"""ZAYA1 (`zaya`): a decoder in which EVERY layer is one compressed
convolutional attention (CCA) sublayer and one expert sublayer.  CCA
projects the residual stream into a latent half its width (8 query
heads and 2 key-value heads of 128 beside a stream of 2048), mixes the
query and key latents over time with two short causal convolutions (a
depthwise one of 2 taps, then one of 2 taps over the channels of one
head), adds the mean of the unmixed query and key latents, norms both to
unit RMS (keys times a learned temperature per key head), rotates the
first half of each head, and attends in the latent.  Half of the value
heads are ONE TOKEN LATE: head 0 of a token's value is projected from
that token, head 1 from the token before it.  The expert sublayer
chooses ONE of 16 SwiGLU experts per token with a router that is an MLP
over a 256-wide state, and that state runs from layer to layer
(`r_l = W_d u + b_d + gamma_l * r_{l-1}`); the chosen expert's weight is
its softmax probability, not renormalised.  Each sublayer joins the
stream through learned scales and shifts (`_join`).  The embedding is
tied to the head.

This module is the model as the serving engine runs it: a config object,
seeded weights, the cache it declares, and its own paged step for a
prefill chunk and for a decode tick, bound into one declared body
(`BODY`, a decode.PagedBody) that the config names, so the engine's two
jitted programs (`engine._prefill_chunk`, `engine._paged_tick`) run it
as they run every model.

The cache (one pytree, `engine._cache`): every layer keeps pages AND
state per decode row.

  k, v   [L, P, page, 2 x 128]   pages: a token's keys (normed, scaled,
                                 rotated: final) and values, its two
                                 heads side by side (the flat form of
                                 ops/paged_attention.py: an array that
                                 ended in [2, 128] would be padded to
                                 the chip's tile); 1,024 B a token and
                                 layer
  cz     [L, rows, 1280]         the latents [q ; k] of a row's LAST
                                 token, before the convolutions
  cc     [L, rows, 1280]         ...after the first convolution
  cv     [L, rows, 128]          the late value head as projected from
                                 the row's last token (the NEXT token's)
  moe    [7, 2] int32            the expert layers' counters
                                 (deepseek_v2.COUNTERS)
  gate   [2, 2] int32            the chosen experts' weights summed (in
                                 units of 2^-10) and the tokens counted

The three tails are state per decode row (`row_state_keys`): what treats
a page as the whole of a sequence's state (prefix cache, tiers, kv_export
/ kv_import, migration, session checkpoints) refuses this model by name
(kv_tier.refuse_row_state).  Nothing zeroes a row's tails when it changes
hands: a chunk that starts at position 0 reads none (a quantity at t - 1
is zero at t = 0), and a tick leaves the tails of a row at position 0 (an
idle row, or the row a prefill is filling) as they are.

A tick reads each row's own pages through the kernel of
ops/paged_attention.py on a TPU and, where there is none, through
exaone_moe's span loop; a chunk walks the row's pages in spans through
exaone_moe's too (`_span_chunk`), and the norms and the partial
rotation are exaone_moe's.  The experts run through
`deepseek_v2.routed_experts` (the Pallas grouped matmul) at `top_k` 1
with all experts held.

What the published config does not pin, and what was taken, is argued in
the benchmark's configuration file (`assumed`).  The routed skip of a
sublayer that the model's description hints at ("MoD") is not here.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.models import deepseek_v2 as _ds
from ray_tpu.models import exaone_moe as _em
from ray_tpu.models.decode import PagedBody
from ray_tpu.ops import paged_attention as _pa

_HI = lax.Precision.HIGHEST
COUNTERS = _ds.COUNTERS
# A chosen expert's weight is counted in units of 2^-10, so that the
# counters stay whole numbers (deepseek_v2._count).
_GATE_UNIT = 1 << 10


@dataclasses.dataclass(frozen=True)
class ZayaConfig:
    """Published ZAYA1-8B sizes by default; `n_layers` says the cut.
    `experts_held` / `expert_offset` are what `deepseek_v2.routed_experts`
    reads: all experts, from the first.  Hashable: the engine passes it
    as a static argument."""
    max_seq: int
    n_layers: int = 40
    vocab_size: int = 262272
    d_model: int = 2048
    n_heads: int = 8
    n_kv_heads: int = 2
    head_dim: int = 128
    rotary_dim: int = 64              # partial_rotary_factor 0.5
    rope_theta: float = 5e6
    moe_d_ff: int = 2048
    n_routed_experts: int = 16
    top_k: int = 1
    router_dim: int = 256
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")
        if self.n_kv_heads != 2:
            raise ValueError("the values are two heads: one of this "
                             "token, one of the token before it")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError("rotary_dim must be even and within a head")
        if not 1 <= self.top_k <= self.n_routed_experts:
            raise ValueError("top_k must be 1..n_routed_experts")

    @property
    def experts_held(self) -> int:
        return self.n_routed_experts

    @property
    def expert_offset(self) -> int:
        return 0

    @property
    def latent(self) -> int:
        """Channels the two convolutions mix: the query heads' and the
        key heads', the query heads first."""
        return (self.n_heads + self.n_kv_heads) * self.head_dim

    @property
    def token_bytes(self) -> int:
        """A token's keys and values in one layer's pages."""
        return 2 * self.n_kv_heads * self.head_dim \
            * jnp.dtype(self.dtype).itemsize

    @property
    def paged_body(self) -> PagedBody:
        return BODY


def _kind(cfg: ZayaConfig) -> _em.AttnKind:
    """The layers' one kind as exaone_moe's functions read it (its span
    loops, its partial rotation): a token's two heads side by side."""
    return _em.AttnKind(cfg.n_kv_heads, cfg.head_dim, cfg.head_dim,
                        rope_theta=cfg.rope_theta,
                        rotary_dim=cfg.rotary_dim, flat=True)


def attn_keys(cfg: ZayaConfig, pos: np.ndarray) -> Tuple[int, int]:
    """(keys read, keys held) by one tick's decode rows at positions
    `pos`, summed over rows and layers: every layer holds and reads all
    `pos + 1`."""
    held = int((np.asarray(pos, np.int64) + 1).sum()) * cfg.n_layers
    return held, held


def attn_keys_gathered(cfg: ZayaConfig, pos: np.ndarray, page_size: int,
                       nblk: int) -> int:
    """Keys one tick pulls from the pool (`pos` of all decode rows, idle
    ones at 0): on a TPU each row's own blocks of pages, what the kernel
    copies; elsewhere whole spans up to the deepest row's token for
    every row."""
    if _em._on_tpu():
        return _pa.keys_copied(pos, page_size, nblk, cfg.token_bytes) \
            * cfg.n_layers
    cols = _ds._span_pages(_em._TICK_SPAN_KEYS, page_size, nblk) * page_size
    spans = -(-(int(np.asarray(pos).max()) + 1) // cols)
    return len(pos) * spans * cols * cfg.n_layers


def check_paging(cfg: ZayaConfig, *, page_size: int, prefill_chunk: int,
                 speculate_k: int) -> None:
    if prefill_chunk % page_size:
        raise ValueError(f"a prefill chunk writes whole pages: "
                         f"prefill_chunk must be a multiple of "
                         f"page_size={page_size}, got {prefill_chunk}")
    if speculate_k:
        raise NotImplementedError(
            "speculative verify on a model with per-row convolution tails "
            "needs the tails rolled back to the accepted token")


# ---------------------------------------------------------------------------
# Weights and cache


def init_params(cfg: ZayaConfig, key, dtype=None) -> Dict:
    """Seeded weights, one dict a layer, drawn so that every mechanism
    moves the logits (a mechanism that seeded weights leave inert is one
    no comparison can hold the program to).  Matrices are normal, std
    0.02, norms' gains 1.  Beside them:

      wo, w_down  projections back into the residual stream at 0.02 / (2
               n_layers), not the other bodies' 0.02 / sqrt(2 n_layers):
               at that size a sublayer's output is three times the
               embedding it is added to, a top-1 swap replaces most of a
               token's stream, and over 20 layers bfloat16 rounding
               alone moves the logits as far as any wrong mechanism does
               (mean difference 0.58 of a spread of 0.90, the chosen
               expert equal on 59 % of (token, layer): PERF.md section
               6, PR 55)

      w0, w1   the convolutions' taps of order 1 (each tap's variance a
               half, so a convolved latent is as large as the latent and
               as the mean part it is added to); their biases a tenth of
               the latent's size
      tau      the keys' temperature, about 4: the norm makes q . k /
               sqrt(d) a cosine times sqrt(d), whose standard deviation
               is 1, so a seeded score's is tau (at 1 attention over
               thousands of keys is near uniform)
      join     the residual scales 1 +- 0.1; the shifts a tenth of an
               embedding's component (+- 0.002): a shift is the same
               vector in every token's stream, and at the embedding's
               own size (0.02) the streams are half common from layer 0
               on, the router sees nearly one input, and a tick of 96
               rows touches 10.8 of 16 experts with the busiest at 6 x
               the mean (PERF.md section 6, PR 55)
      gamma    the router state's carry, 0.5 to 1
      router   its MLP in float32, as it is applied; hidden layers at
               unit gain; the two layers that read a gelu's outputs
               drawn with columns that sum to zero over their inputs
               (those outputs have a mean, which would otherwise favour
               some experts for every token); the last scaled so that a
               chosen expert's probability is about 0.4 on average; the
               balancing bias zero
    """
    dtype = dtype or cfg.dtype
    D, H, G, d, F, R, E = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                           cfg.head_dim, cfg.moe_d_ff, cfg.router_dim,
                           cfg.n_routed_experts)
    f32 = jnp.float32
    nrm, _, s, _ = _em.seeded_draws(cfg, key, dtype)
    out = s / (2 * cfg.n_layers)
    draws = iter(jax.random.split(jax.random.fold_in(key, 1),
                                  24 * cfg.n_layers))
    ones = lambda *shape: jnp.ones(shape, f32)  # noqa: E731
    z_size = s * D ** 0.5             # a latent's component, u at unit RMS

    def small(shape, scale, mean=0.0):
        return mean + scale * jax.random.normal(next(draws), shape, f32)

    def centred(shape, scale):
        w = small(shape, scale)
        return w - w.mean(0, keepdims=True)

    def join():
        return jnp.stack([small((D,), 0.1, 1.0), small((D,), 0.1 * s),
                          small((D,), 0.1, 1.0), small((D,), 0.1 * s)])

    def layer(l):
        lp = {"ln1": ones(D), "wqk": nrm((D, H + G, d), s),
              "wv": nrm((D, G, d), s),
              "w0": small((2, H + G, d), 0.5 ** 0.5),
              "b0": small((H + G, d), 0.1 * z_size),
              "w1": nrm((2, H + G, d, d), (2 * d) ** -0.5),
              "b1": small((H + G, d), 0.1 * z_size),
              "tau": small((G,), 0.5, 4.0),
              "wo": nrm((H, d, D), out), "join1": join(),
              "ln2": ones(D), "join2": join(),
              "router": {
                  "wd": nrm((D, R), s, f32), "bd": small((R,), 0.1 * z_size),
                  "ln": ones(R),
                  "w1": small((R, R), R ** -0.5), "b1": small((R,), 0.1),
                  "w2": centred((R, R), R ** -0.5), "b2": small((R,), 0.1),
                  "w3": centred((R, E), 5.0 * R ** -0.5),
                  "beta": jnp.zeros((E,), f32)},
              "experts": {"w_gate": nrm((E, D, F), s),
                          "w_up": nrm((E, D, F), s),
                          "w_down": nrm((E, F, D), out)}}
        if l:
            lp["router"]["gamma"] = jax.random.uniform(
                next(draws), (R,), f32, 0.5, 1.0)
        return lp

    return {"wte": nrm((cfg.vocab_size, D), s),
            "layers": tuple(layer(l) for l in range(cfg.n_layers)),
            "ln_f": ones(D)}


def init_paged_cache(cfg: ZayaConfig, num_pages: int, page_size: int,
                     num_slots: Optional[int] = None) -> Dict:
    L, rows = cfg.n_layers, num_slots or 1
    flat = cfg.n_kv_heads * cfg.head_dim
    page = lambda: jnp.zeros((L, num_pages, page_size, flat),  # noqa: E731
                             cfg.dtype)
    tail = lambda width: jnp.zeros((L, rows, width), cfg.dtype)  # noqa: E731
    return {"k": page(), "v": page(), "cz": tail(cfg.latent),
            "cc": tail(cfg.latent), "cv": tail(cfg.head_dim),
            "moe": jnp.zeros((len(COUNTERS), 2), jnp.int32),
            "gate": jnp.zeros((2, 2), jnp.int32)}


def snapshot_counters(cache: Dict) -> Dict:
    """deepseek_v2.snapshot_counters, with the gate's two beside the
    expert layers'."""
    snap = _ds.snapshot_counters(cache)
    snap["gate"] = jnp.copy(cache["gate"])
    snap["gate"].copy_to_host_async()
    return snap


def read_counters(cache: Dict, cfg) -> Dict[str, Any]:
    """The expert layers' counters (deepseek_v2.read_counters) and
    `gate_mass`: the weight its chosen expert gave a token (its softmax
    probability), summed over ticks' live rows, chunks' real tokens and
    layers, beside `gate_tokens`, how many that sums.  Their ratio is the
    mean weight a chosen expert gets: between 1 / experts and 1 under a
    live router, exactly 1 if something renormalised a top-1."""
    counts = _ds.read_counters(cache, cfg)
    mass, tokens = (int((hi << _ds._WORD) + lo) for hi, lo in
                    np.asarray(cache["gate"]).astype(np.int64))
    counts["gate_mass"] = mass / _GATE_UNIT
    counts["gate_tokens"] = tokens
    return counts


_rms = _em._rms          # reads `cfg.rms_eps` and nothing else


def _join(x, f, j):
    """Residual scaling: (a * x + c) + (a' * f + c'), `j` the four
    vectors [a, c, a', c'] of one sublayer."""
    return ((j[0] * x + j[1]) + (j[2] * f + j[3])).astype(x.dtype)


# ---------------------------------------------------------------------------
# The expert sublayer


def route(rp, h, r_prev, cfg: ZayaConfig):
    """The router MLP over the state that runs from layer to layer, in
    float32.  h [N, D], r_prev [N, R] (None in layer 0) -> (expert ids
    [N, top_k], weights [N, top_k] float32, this layer's state [N, R]):
    r = W_d h + b_d + gamma * r_prev; two gelu layers over rms(r); a
    softmax over ALL experts; the top_k largest of p + beta are chosen
    and weigh p itself (a top-1 is NOT renormalised)."""
    def dot(a, w):
        return jnp.einsum("nr,rs->ns", a, w, precision=_HI)
    r = dot(h.astype(jnp.float32), rp["wd"]) + rp["bd"]
    if r_prev is not None:
        r = r + rp["gamma"] * r_prev
    t = jax.nn.gelu(dot(_rms(r, rp["ln"], cfg), rp["w1"]) + rp["b1"],
                    approximate=False)
    t = jax.nn.gelu(dot(t, rp["w2"]) + rp["b2"], approximate=False)
    p = jax.nn.softmax(dot(t, rp["w3"]), axis=-1)
    ids = lax.top_k(p + rp["beta"][None], cfg.top_k)[1]
    return ids.astype(jnp.int32), jnp.take_along_axis(p, ids, axis=1), r


def _moe(lp, x, r_prev, live, is_tick, counts, mass, cfg: ZayaConfig):
    """join2(x, MoE(norm(x), r_prev)).  `counts`: this call's additions
    to COUNTERS so far; `mass`: to the chosen experts' summed weights."""
    h = _rms(x, lp["ln2"], cfg)
    with jax.named_scope("moe_route"):
        ids, weights, r = route(lp["router"], h, r_prev, cfg)
    with jax.named_scope("moe_experts"):
        routed, sizes = _ds.routed_experts(lp["experts"], h, ids, weights,
                                           live, cfg)
    counts = _ds.count_routed(counts, live, sizes, is_tick, cfg)
    mass = mass + (weights.sum(-1) * live).sum()
    return _join(x, routed, lp["join2"]), r, counts, mass


# ---------------------------------------------------------------------------
# Compressed convolutional attention, for a single-row chunk of T tokens
# (x [T, D]) and for a tick of B rows (x [B, D]).  `z_prev`, `c0_prev`
# and `v_prev` are what the token BEFORE each token left: the rows' tails
# in a tick, the chunk shifted by one behind the row's tails in a chunk.


def _latents(lp, x, cfg: ZayaConfig):
    """x [n, D] -> the latents z [n, H + G, d] (query heads first) and
    both value heads as projected from these tokens [n, G, d]."""
    dt = cfg.dtype
    u = _rms(x, lp["ln1"], cfg)
    return (jnp.einsum("nd,dhk->nhk", u, lp["wqk"].astype(dt)),
            jnp.einsum("nd,dgk->ngk", u, lp["wv"].astype(dt)))


def _conv0(lp, z, z_prev):
    """The depthwise convolution over time: [n, H + G, d] of the cache's
    type."""
    return (lp["w0"][0] * z_prev + lp["w0"][1] * z + lp["b0"]
            ).astype(z.dtype)


def _mix(lp, z, c0, c0_prev, positions, cfg: ZayaConfig):
    """The second convolution (over time and over the channels of one
    head), the q-k mean of the UNMIXED latents, the norm, the keys'
    temperature and the rotation: z, c0, c0_prev [n, H + G, d] ->
    q [n, H, d], k [n, G, d], both final."""
    dt = cfg.dtype
    H, G = cfg.n_heads, cfg.n_kv_heads
    w1 = lp["w1"].astype(dt)
    c1 = jnp.einsum("ngk,gkj->ngj", c0_prev, w1[0],
                    preferred_element_type=jnp.float32) \
        + jnp.einsum("ngk,gkj->ngj", c0, w1[1],
                     preferred_element_type=jnp.float32) + lp["b1"]
    z32 = z.astype(jnp.float32)
    n, d = z.shape[0], cfg.head_dim
    zq = z32[:, :H].reshape(n, G, H // G, d)
    mq = (zq + z32[:, H:, None]) / 2                     # [n, G, R, d]
    q = c1[:, :H] + mq.reshape(n, H, d)
    k = c1[:, H:] + mq.mean(2)
    # unit RMS, no gain; the keys' gain is their head's temperature
    q = _rms(q, 1.0, cfg).astype(dt)
    k = _rms(k, lp["tau"][:, None], cfg).astype(dt)
    kind = _kind(cfg)
    return _em._rotate(q, positions, kind), _em._rotate(k, positions, kind)


def _close(lp, x, out, cfg):
    return _join(x, jnp.einsum("nhk,hkd->nd", out, lp["wo"].astype(cfg.dtype)),
                 lp["join1"])


def _attn_chunk(lp, x, l, cache, bt, start, slot, valid, cfg: ZayaConfig):
    T = x.shape[0]
    G, d = cfg.n_kv_heads, cfg.head_dim
    psz = cache["k"].shape[2]
    cols = start + jnp.arange(T)
    last = valid - 1                                  # the last REAL token

    with jax.named_scope("cca_mix"):
        z, vv = _latents(lp, x, cfg)

        def behind(tail, a):
            """`a` one token late: the row's tail (zeros where the row
            begins here) in front of all but the last of `a`."""
            tail = jnp.where(start == 0, 0, tail).reshape((1,) + a.shape[1:])
            return jnp.concatenate([tail.astype(a.dtype), a[:-1]])
        c0 = _conv0(lp, z, behind(cache["cz"][l, slot], z))
        q, k = _mix(lp, z, c0, behind(cache["cc"][l, slot], c0), cols, cfg)
        v = jnp.concatenate([vv[:, 0], behind(cache["cv"][l, slot],
                                              vv[:, 1])], axis=-1)
        at = lambda a: lax.dynamic_index_in_dim(  # noqa: E731
            a, last, 0, keepdims=False).reshape(-1)
        tails = {"cz": cache["cz"].at[l, slot].set(at(z)),
                 "cc": cache["cc"].at[l, slot].set(at(c0)),
                 "cv": cache["cv"].at[l, slot].set(at(vv[:, 1]))}
        pages = lax.dynamic_slice(bt, (start // psz,), (T // psz,))
        ck = cache["k"].at[l, pages].set(k.reshape(T // psz, psz, G * d))
        cv = cache["v"].at[l, pages].set(v.reshape(T // psz, psz, G * d))

    with jax.named_scope("attn_latent"):
        out = _em._span_chunk(q, ck, cv, l, bt, start, _kind(cfg))
    return _close(lp, x, out, cfg), dict(cache, k=ck, v=cv, **tails)


def _attn_tick(lp, x, l, cache, bt, pos, cfg: ZayaConfig):
    B = x.shape[0]
    G, d = cfg.n_kv_heads, cfg.head_dim
    psz = cache["k"].shape[2]

    with jax.named_scope("cca_mix"):
        z, vv = _latents(lp, x, cfg)
        shaped = lambda name: cache[name][l].reshape(z.shape)  # noqa: E731
        c0 = _conv0(lp, z, shaped("cz"))
        q, k = _mix(lp, z, c0, shaped("cc"), pos, cfg)
        v = jnp.concatenate([vv[:, 0], cache["cv"][l]], axis=-1)
        # a row at position 0 is idle, or the row a prefill is filling:
        # its tails stay as they are
        active = (pos > 0)[:, None]
        keep = lambda name, new: cache[name].at[l].set(  # noqa: E731
            jnp.where(active, new.reshape(B, -1), cache[name][l]))
        tails = {"cz": keep("cz", z), "cc": keep("cc", c0),
                 "cv": keep("cv", vv[:, 1])}
        page = jnp.take_along_axis(bt, (pos // psz)[:, None], axis=1)[:, 0]
        ck = cache["k"].at[l, page, pos % psz].set(k.reshape(B, G * d))
        cv = cache["v"].at[l, page, pos % psz].set(v)

    with jax.named_scope("attn_latent"):
        if _em._on_tpu():
            out = _pa.paged_attention(q, ck, cv, l, bt, pos, n_kv_heads=G)
        else:
            out = _em._span_tick(q, ck, cv, l, bt, pos, _kind(cfg))
    return _close(lp, x, out, cfg), dict(cache, k=ck, v=cv, **tails)


# ---------------------------------------------------------------------------
# The paged step


def _through_layers(params, x, cache, live, is_tick, attend, cfg):
    counts = [jnp.int32(0)] * len(COUNTERS)
    mass, r = jnp.float32(0), None
    for l, lp in enumerate(params["layers"]):
        x, cache = attend(lp, x, l, cache)
        x, r, counts, mass = _moe(lp, x, r, live, is_tick, counts, mass, cfg)
    x = _rms(x, params["ln_f"], cfg)
    logits = jnp.einsum("nd,vd->nv", x.astype(cfg.dtype),
                        params["wte"].astype(cfg.dtype),
                        preferred_element_type=jnp.float32)
    gate = [jnp.round(mass * _GATE_UNIT),
            live.sum() * cfg.top_k * cfg.n_layers]
    return logits, dict(cache, moe=_ds._count(cache["moe"], counts),
                        gate=_ds._count(cache["gate"], gate))


def paged_chunk_step(params: Dict, tokens, pos, cache: Dict, block_tables,
                     cfg: ZayaConfig, pad_lo=None, slot=None,
                     valid=None) -> Tuple[Any, Dict]:
    """The model's paged step, under decode.paged_chunk_step's contract.

    `pos` a scalar: ONE row's chunk of T tokens starting there (T and
    `pos` whole pages) — single-row prefill.  It fills the row's pages,
    starts from the tails of decode row `slot` (default 0) where `pos` >
    0 and from zeros at 0, and leaves there the tails of the last of its
    first `valid` tokens (default all); only those tokens are routed to
    experts.  `pos` a [B] vector with one token a row: the decode tick.
    Rows at position 0 are idle: their page writes land wherever their
    block table points (the trash page), their tails are not written,
    and they are routed nowhere.
    Returns (logits [B, t, V] float32, cache)."""
    if pad_lo is not None:
        raise NotImplementedError("left-padded rows")
    B, t = tokens.shape
    psz = cache["k"].shape[2]
    pos = jnp.asarray(pos, jnp.int32)
    embed = lambda tok: jnp.take(params["wte"], tok, axis=0  # noqa: E731
                                 ).astype(cfg.dtype)
    if pos.ndim == 0:
        if B != 1 or t % psz:
            raise ValueError(f"a chunk is one row of whole pages of {psz} "
                             f"tokens, got {tokens.shape}")
        slot = jnp.int32(0) if slot is None else jnp.asarray(slot, jnp.int32)
        valid = jnp.int32(t) if valid is None \
            else jnp.asarray(valid, jnp.int32)
        bt = block_tables[0]
        logits, cache = _through_layers(
            params, embed(tokens[0]), cache, jnp.arange(t) < valid, False,
            lambda lp, x, l, c: _attn_chunk(lp, x, l, c, bt, pos, slot,
                                            valid, cfg), cfg)
        return logits[None], cache
    if t != 1:
        raise NotImplementedError(
            "several tokens a row at per-row positions (speculative "
            "verify) need the convolution tails rolled back on rejection")
    logits, cache = _through_layers(
        params, embed(tokens[:, 0]), cache, pos > 0, True,
        lambda lp, x, l, c: _attn_tick(lp, x, l, c, block_tables, pos, cfg),
        cfg)
    return logits[:, None], cache


BODY = PagedBody(
    init_paged_cache=init_paged_cache, paged_chunk_step=paged_chunk_step,
    check_paging=check_paging, attn_keys=attn_keys,
    row_state_keys=("cz", "cc", "cv"),
    attn_keys_gathered=attn_keys_gathered,
    snapshot_counters=snapshot_counters, read_counters=read_counters)
