"""Ling-3.0-flash (`bailing_hybrid`): a decoder whose layers come in
groups of six, five Kimi-Delta-Attention layers (KDA, arXiv:2510.26692)
and then one layer of multi-head latent attention (MLA), each followed
by a feed-forward: a dense SwiGLU in the leading layers, then one shared
and 512 routed SwiGLU experts chosen 8 a token by a sigmoid router
(DeepSeek-V3's `noaux_tc`: a bias that moves the choice and not the
weight, a limit to the best 4 of 8 groups, weights renormalised over the
8 chosen and scaled).  This module is the model as the serving engine
runs it: a config object, seeded weights, the cache it declares, and its
own paged step for a prefill chunk and for a decode tick, bound into one
declared body (`BODY`, a decode.PagedBody) that the config names, so the
engine's two jitted programs (`engine._prefill_chunk`,
`engine._paged_tick`) run it as they run every model.

A KDA layer keeps NO keys: per sequence it holds a delta-rule state of
[32 heads, 128 (key), 128 (value)] float32 (2 MiB) and the last 3 inputs
of the 4-tap depthwise convolution over its q, k and v streams, whatever
the context.  An MLA layer keeps one latent row a token, exactly
deepseek_v2's (512 normed latent + 64 rotated key part + 64 zeros), and
runs deepseek_v2's two attention paths over it (`_attn_chunk` expands,
`_attn_tick` absorbs) with a projection and a gate of its own: ONE
full-rank query projection (`q_lora_rank` null), interleaved RoPE at
theta 6e6 with no scaling, and a sigmoid gate a head on the heads'
outputs before `wo`.  The cache (one pytree, `engine._cache`):

  lat    [A, P, page, 640]              the A MLA layers' latent pool
  kda    [M, rows, 32, 128, 128] f32    the M KDA layers' state, one per
                                        decode row
  conv   [M, rows, 3 x 12288]           the convolution's tail: a row's
                                        last 3 REAL inputs (q | k | v
                                        side by side) oldest first,
                                        channels minor, in the model's
                                        dtype (jamba.py's layout)
  moe    [7, 2] int32                   the expert layers' counters
                                        (deepseek_v2.COUNTERS)
  kdac   [6, 2] int32                   KDA_COUNTERS, below

`kda` and `conv` are state per decode row (`row_state_keys`) and `lat`
is a latent page (not `framed`): the FIRST body with both, so what
treats a page as the whole of a sequence's state (the prefix cache)
refuses it for the state (kv_tier.refuse_row_state) and what frames
pages (tiers, kv_export / kv_import, migration, session checkpoints)
for the state and the page alike (kv_tier.refuse_unframed).

The row-state contract (minicpm_sala.py, exaone_moe.py, jamba.py): the
chunk that starts at position 0 zeroes `slot`'s state and tail inside
the program; a chunk moves them by its first `valid` tokens only (a
pad's log-decay and write strength are 0: it neither decays nor writes,
and the tail is read before the pads); a tick steps every row whose
position is past 0 and leaves the others exactly as they are.

Device code: `ray_tpu/ops/kda.py` (scopes `kda_conv`, `kda_gate`,
`kda_chunk`, `kda_step`: on a TPU the last two are one Pallas kernel a
layer each, the chunk's told `valid` so that it skips the chunks of 64
tokens that are all pad; plain XLA elsewhere), deepseek_v2's latent
attention (`mla_expand_attend`, `mla_absorb_attend`) and its expert
walk (`moe_route`, `moe_experts`), the head under `lm_head`.

The expert layer is told which experts it holds (`experts_held`,
`expert_offset`), as deepseek_v2's is: the router scores ALL 512, and a
chosen expert that is not held adds nothing.

What the published config leaves to the family's convention is argued
in the benchmark's configuration file (`assumed`).  The multi-token
prediction layer is not here.
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
from ray_tpu.models.decode import PagedBody, _swiglu
from ray_tpu.ops import kda

KDA, MLA = "kda", "mla"
_HI = lax.Precision.HIGHEST
COUNTERS = _ds.COUNTERS
# `decay_mass`: the decay alpha = exp(a), averaged over a token's 4,096
# key channels, summed over ticks' live rows, chunks' real tokens and
# KDA layers, in units of 2^-10 so that the counter stays whole;
# `decay_count`: how many (token, layer) that sums.  `rows_stepped`: the
# row states a tick's step read and wrote, summed over KDA layers;
# `rows_live`: those of the rows that yielded a token.
# `chunk_tokens_walked`: the tokens a prefill chunk's delta rule walked
# (whole chunks of 64 up to the last real token where ops/kda.py's
# kernel runs, every token of the call elsewhere), summed over KDA
# layers; `chunk_tokens_real`: the real ones among them.
KDA_COUNTERS = ("decay_mass", "decay_count", "rows_stepped", "rows_live",
                "chunk_tokens_walked", "chunk_tokens_real")
_UNIT = 1 << 10


@dataclasses.dataclass(frozen=True)
class BailingHybridConfig:
    """Published Ling-3.0-flash sizes by default; `n_layers`,
    `layer_offset` (the published index of the first layer run here),
    `first_k_dense`, `experts_held`, `expert_offset` and `vocab_size`
    say the share this chip holds.  Hashable: the engine passes it as a
    static argument."""
    max_seq: int
    n_layers: int = 42
    layer_offset: int = 0
    layer_group_size: int = 6         # the last of a group is MLA
    vocab_size: int = 157184
    d_model: int = 2560
    n_heads: int = 32
    head_dim: int = 128               # a KDA head: key and value
    conv_kernel: int = 4
    kda_lower_bound: float = -5.0     # a token's least log-decay
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6e6
    d_ff: int = 6144                  # the leading dense layers
    first_k_dense: int = 2            # of the layers run here
    moe_d_ff: int = 768
    n_routed_experts: int = 512       # what the router scores: never cut
    n_group: int = 8
    topk_group: int = 4
    top_k: int = 8
    routed_scaling_factor: float = 2.5
    experts_held: Optional[int] = None    # None: all of them
    expert_offset: int = 0
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.experts_held is None:
            object.__setattr__(self, "experts_held", self.n_routed_experts)
        if self.n_routed_experts % self.n_group:
            raise ValueError("n_routed_experts must be whole groups")
        if not 0 < self.topk_group <= self.n_group:
            raise ValueError("topk_group must be 1..n_group")
        per = self.n_routed_experts // self.n_group
        if per < 2 or self.top_k > self.topk_group * per:
            raise ValueError("a group scores its two best, and top_k must "
                             "fit the kept groups")
        if self.expert_offset < 0 or self.experts_held < 1 \
                or self.expert_offset + self.experts_held \
                > self.n_routed_experts:
            raise ValueError("the held experts must lie among the routed")
        if not 0 <= self.first_k_dense <= self.n_layers:
            raise ValueError("first_k_dense must be 0..n_layers")
        if self.conv_kernel < 2 or self.kda_lower_bound >= 0:
            raise ValueError("the convolution keeps conv_kernel - 1 >= 1 "
                             "inputs and a log-decay's bound is negative")

    @property
    def kinds(self) -> Tuple[str, ...]:
        """Each layer's mixer, by its PUBLISHED index: the last of every
        `layer_group_size` is MLA."""
        return tuple(
            MLA if (self.layer_offset + i + 1) % self.layer_group_size == 0
            else KDA for i in range(self.n_layers))

    @property
    def n_mla(self) -> int:
        return sum(k == MLA for k in self.kinds)

    @property
    def n_kda(self) -> int:
        return self.n_layers - self.n_mla

    @property
    def n_moe(self) -> int:
        return self.n_layers - self.first_k_dense

    @property
    def kda_width(self) -> int:
        """Channels of one of the q, k, v streams."""
        return self.n_heads * self.head_dim

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    @property
    def paged_body(self) -> PagedBody:
        return BODY


def attn_keys(cfg: BailingHybridConfig, pos: np.ndarray) -> Tuple[int, int]:
    """(keys read, keys held) by one tick's decode rows at positions
    `pos`, summed over rows and the MLA layers: each reads all it holds.
    (A KDA layer holds no key.)"""
    held = (int(np.asarray(pos, np.int64).sum()) + len(pos)) * cfg.n_mla
    return held, held


def attn_keys_gathered(cfg: BailingHybridConfig, pos: np.ndarray,
                       page_size: int, nblk: int) -> int:
    """Latents one tick pulls from the pool, for EVERY row of the call:
    deepseek_v2's count, in the MLA layers."""
    return _ds.attn_keys_gathered(cfg, pos, page_size, nblk,
                                  layers=cfg.n_mla)


def attn_keys_paged(cfg: BailingHybridConfig, pos: np.ndarray,
                    all_pos: np.ndarray, page_size: int, nblk: int
                    ) -> Tuple[int, int]:
    """(keys gathered, keys held) in the layers whose keys live in pages:
    the MLA layers, the only ones that hold any."""
    return (attn_keys_gathered(cfg, all_pos, page_size, nblk),
            attn_keys(cfg, pos)[1])


def check_paging(cfg: BailingHybridConfig, *, page_size: int,
                 prefill_chunk: int, speculate_k: int) -> None:
    if prefill_chunk % page_size:
        raise ValueError(f"a prefill chunk writes whole latent pages: "
                         f"prefill_chunk must be a multiple of page_size="
                         f"{page_size}, got {prefill_chunk}")
    if prefill_chunk % min(kda.CHUNK, prefill_chunk) \
            or prefill_chunk % kda.SUB:
        raise ValueError(f"the delta rule walks chunks of {kda.CHUNK} "
                         f"tokens in sub-blocks of {kda.SUB}: prefill_chunk "
                         f"must be whole ones, got {prefill_chunk}")
    if speculate_k:
        raise NotImplementedError(
            "speculative verify on a model with per-row recurrent state "
            "needs the state rolled back to the accepted token")


# ---------------------------------------------------------------------------
# Weights and cache


def seeded_mla_gain(cfg: BailingHybridConfig, logit_std: float = 4.0
                    ) -> float:
    """What W_q of an MLA layer is scaled by so that a seeded attention
    logit has standard deviation `logit_std`: unit-RMS inputs through
    matrices of std 0.02 give q and the rotary key components of std
    0.02 sqrt(d_model) and k_nope ones of 0.02 sqrt(kv_lora_rank)."""
    var = 0.02 ** 2
    q, k_nope, k_pe = (var * cfg.d_model, var * cfg.kv_lora_rank,
                       var * cfg.d_model)
    std = (cfg.qk_nope_head_dim * q * k_nope
           + cfg.qk_rope_head_dim * q * k_pe) ** 0.5 * cfg.softmax_scale
    return logit_std / std


def init_params(cfg: BailingHybridConfig, key, dtype=None) -> Dict:
    """Seeded weights, one dict a layer, drawn so that every mechanism
    moves the logits (a mechanism that seeded weights leave inert is one
    no comparison can hold the program to).  Matrices are normal, std
    0.02 (projections back into the residual stream 0.02 / sqrt(2
    n_layers)), norms' gains 1.  Beside them, all float32:

      conv     the three streams' taps, normal of std conv_kernel^-0.5: a
               convolved stream is as large as the stream
      a_log,   the decay a_t = -5 sigmoid(exp(a_log) (g_t + dt_bias)),
      dt_bias  g_t = h Wf of std ~1 (W_f at 0.02 on a unit-RMS input of
               2,560): `dt_bias` uniform in [-9, -3] a channel and
               `exp(a_log)` in [0.7, 1.4] a head, so a channel's
               half-life ln 2 / (5 sigmoid(z)) lies from ~1 token to
               tens of thousands (median ~50), moves with the token by a
               factor of e either way, and never sits on the bound (a
               test drives the kernel there)
      wb       beta = sigmoid(h W_b), logits of std ~1: 0.1 to 0.9
      wq (MLA) scaled (`seeded_mla_gain`) so that a seeded attention
               logit has standard deviation 4: a handful of keys hold
               most of a head's weight, as in a trained model (at 0.7,
               what std 0.02 gives, attention over thousands of keys is
               near uniform and positions wrongly applied move no logit)
      router   float32, as it is applied: scores sigmoid(N(0, 1)); the
               selection bias normal of std 0.02, a tenth of the scores'
               spread and several times the gap between the 8th and 9th
               best of 512: it moves choices, and a zero bias could be
               seen by no check
    """
    dtype = dtype or cfg.dtype
    D, H, d, F = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.moe_d_ff
    E, K = cfg.kda_width, cfg.conv_kernel
    kr, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                      cfg.qk_rope_head_dim, cfg.v_head_dim)
    f32 = jnp.float32
    s = 0.02
    so = s / np.sqrt(2 * cfg.n_layers)
    keys = iter(jax.random.split(key, 2 + 24 * cfg.n_layers))

    def nrm(shape, scale, dt=dtype):
        return (scale * jax.random.normal(next(keys), shape, f32)).astype(dt)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, f32, lo, hi)

    ones = lambda *shape: jnp.ones(shape, f32)  # noqa: E731

    def swiglu(width, *lead):
        return {"w_gate": nrm(lead + (D, width), s),
                "w_up": nrm(lead + (D, width), s),
                "w_down": nrm(lead + (width, D), so)}

    def mixer(kind):
        if kind == MLA:
            return {"wq": nrm((D, H, dn + dr), s * seeded_mla_gain(cfg)),
                    "wkv_a": nrm((D, kr + dr), s), "kv_norm": ones(kr),
                    "wk_b": nrm((H, dn, kr), s), "wv_b": nrm((H, kr, dv), s),
                    "wg": nrm((D, H), s), "wo": nrm((H, dv, D), so)}
        return {"wqkv": nrm((D, 3 * E), s), "wf": nrm((D, E), s),
                "wb": nrm((D, H), s), "wg": nrm((D, E), s),
                "conv": nrm((K, 3 * E), K ** -0.5, f32),
                "a_log": uniform((H,), np.log(0.7), np.log(1.4)),
                "dt_bias": uniform((E,), -9.0, -3.0),
                "o_norm": ones(d), "wo": nrm((E, D), so)}

    def layer(i, kind):
        lp = dict(mixer(kind), ln1=ones(D), ln2=ones(D))
        if i < cfg.first_k_dense:
            return dict(lp, **swiglu(cfg.d_ff))
        return dict(lp, router=nrm((D, cfg.n_routed_experts), s, f32),
                    router_bias=nrm((cfg.n_routed_experts,), s, f32),
                    shared=swiglu(F), experts=swiglu(F, cfg.experts_held))

    return {"wte": nrm((cfg.vocab_size, D), s),
            "layers": tuple(layer(i, kind)
                            for i, kind in enumerate(cfg.kinds)),
            "ln_f": ones(D), "wlm": nrm((D, cfg.vocab_size), s)}


def init_paged_cache(cfg: BailingHybridConfig, num_pages: int,
                     page_size: int, num_slots: Optional[int] = None
                     ) -> Dict:
    rows = num_slots or 1
    return {"lat": jnp.zeros((cfg.n_mla, num_pages, page_size,
                              _ds._lat_width(cfg)), cfg.dtype),
            "kda": jnp.zeros((cfg.n_kda, rows, cfg.n_heads, cfg.head_dim,
                              cfg.head_dim), jnp.float32),
            "conv": jnp.zeros((cfg.n_kda, rows, (cfg.conv_kernel - 1)
                               * 3 * cfg.kda_width), cfg.dtype),
            "moe": jnp.zeros((len(COUNTERS), 2), jnp.int32),
            "kdac": jnp.zeros((len(KDA_COUNTERS), 2), jnp.int32)}


def snapshot_counters(cache: Dict) -> Dict:
    """deepseek_v2.snapshot_counters, with the KDA layers' beside the
    expert layers'."""
    snap = _ds.snapshot_counters(cache)
    snap["kdac"] = jnp.copy(cache["kdac"])
    snap["kdac"].copy_to_host_async()
    return snap


def read_counters(cache: Dict, cfg) -> Dict[str, Any]:
    """The expert layers' counters (deepseek_v2.read_counters, as
    `moe_<name>` in the engine's stats) and KDA_COUNTERS beside them
    (`kda_<name>`): `kda_decay_mass / kda_decay_count` is the mean
    decay a channel a token, strictly between e^-5 and 1 on a live gate
    and exactly 1 if something dropped the decay; `kda_rows_stepped /
    kda_rows_live` is 1 where a tick's step touched the state of live
    rows alone; `kda_chunk_tokens_walked / kda_chunk_tokens_real` is 1
    where a prefill chunk's delta rule walked no pad."""
    counts = _ds.read_counters(cache, cfg)
    for name, (hi, lo) in zip(KDA_COUNTERS,
                              np.asarray(cache["kdac"]).astype(np.int64)):
        counts["kda_" + name] = int((hi << _ds._WORD) + lo)
    counts["kda_decay_mass"] /= _UNIT
    return counts


_rms = _em._rms          # reads `cfg.rms_eps` and nothing else


# ---------------------------------------------------------------------------
# The expert layer


def route(router, bias, h, cfg: BailingHybridConfig):
    """DeepSeek-V3's `noaux_tc` over ALL routed experts, in float32.
    h [N, D] -> (expert ids [N, top_k], weights [N, top_k] float32):
    scores s = sigmoid(h W_r); FOR THE CHOICE ONLY s' = s + bias; a
    group scores the sum of its two largest s'; the `topk_group` best
    groups are kept; the top_k largest s' inside them are chosen; their
    weights are the UNBIASED scores renormalised to sum to one, times
    `routed_scaling_factor`."""
    N = h.shape[0]
    s = jax.nn.sigmoid(jnp.einsum(
        "nd,de->ne", h.astype(jnp.float32), router.astype(jnp.float32),
        precision=_HI))
    chosen_by = s + bias[None]
    per = cfg.n_routed_experts // cfg.n_group
    best = lax.top_k(chosen_by.reshape(N, cfg.n_group, per), 2)[0].sum(-1)
    kept = lax.top_k(best, cfg.topk_group)[1]                # [N, groups]
    in_kept = (kept[:, :, None] == jnp.arange(cfg.n_group)[None, None]
               ).any(1)                                      # [N, n_group]
    ids = lax.top_k(jnp.where(jnp.repeat(in_kept, per, axis=1), chosen_by,
                              -jnp.inf), cfg.top_k)[1]
    w = jnp.take_along_axis(s, ids, axis=1)
    return ids.astype(jnp.int32), \
        w / w.sum(-1, keepdims=True) * cfg.routed_scaling_factor


def _ffn(lp, x, live, is_tick, counts, cfg: BailingHybridConfig):
    """x + FFN(norm(x)): dense SwiGLU in the leading layers, shared +
    held routed experts after them.  `counts`: this call's additions to
    COUNTERS so far."""
    dt = cfg.dtype
    h = _rms(x, lp["ln2"], cfg)
    if "router" not in lp:
        return x + _swiglu(lp, h, dt), counts
    with jax.named_scope("moe_route"):
        ids, weights = route(lp["router"], lp["router_bias"], h, cfg)
    with jax.named_scope("moe_experts"):
        routed, sizes = _ds.routed_experts(lp["experts"], h, ids, weights,
                                           live, cfg)
    counts = _ds.count_routed(counts, live, sizes, is_tick, cfg)
    return x + (routed + _swiglu(lp["shared"], h, dt)).astype(x.dtype), counts


# ---------------------------------------------------------------------------
# The KDA mixer, for a single-row chunk of T tokens (x [T, D]) and for a
# tick of B rows (x [B, D]).  `i` indexes the layer among the KDA layers
# (its state and tail).


def _kda_in(lp, x, cfg: BailingHybridConfig):
    """x [n, D] -> the q | k | v streams before the convolution [n, 3E]
    (the model's dtype), the decay gate's input g [n, E] and the write
    strength beta [n, H] (float32), the output gate's input [n, E]."""
    dt = cfg.dtype
    h = _rms(x, lp["ln1"], cfg)
    dot = lambda w, out=None: jnp.einsum(  # noqa: E731
        "nd,de->ne", h, lp[w].astype(dt), preferred_element_type=out)
    beta = jax.nn.sigmoid(dot("wb", jnp.float32))
    return dot("wqkv"), dot("wf", jnp.float32), beta, dot("wg")


def _kda_gate(lp, g, c, cfg: BailingHybridConfig):
    """g [n, E] float32 and the convolved streams c [n, 3E] float32 ->
    (q, k, v, a), each [n, H, d] float32: q and k of unit length (q over
    sqrt(d) besides), `a` the log of the decay, in (lower bound, 0)."""
    n, H, d = g.shape[0], cfg.n_heads, cfg.head_dim
    heads = lambda x: x.reshape(n, H, d)                     # noqa: E731
    q, k, v = (heads(c[:, j * H * d:(j + 1) * H * d]) for j in range(3))
    unit = lambda x: x * lax.rsqrt(                          # noqa: E731
        (x * x).sum(-1, keepdims=True) + cfg.rms_eps)
    a = cfg.kda_lower_bound * jax.nn.sigmoid(
        jnp.exp(lp["a_log"])[None, :, None]
        * (heads(g) + lp["dt_bias"].reshape(1, H, d)))
    return unit(q) * d ** -0.5, unit(k), v, a


def _kda_out(lp, x, o, gate, cfg: BailingHybridConfig):
    """The heads' outputs o [n, H, d] float32, normed a head, gated
    element-wise, projected and added to the stream."""
    n = o.shape[0]
    y = _rms(o, lp["o_norm"], cfg).reshape(n, -1) \
        * jax.nn.sigmoid(gate.astype(jnp.float32))
    return x + jnp.einsum("ne,ed->nd", y.astype(cfg.dtype),
                          lp["wo"].astype(cfg.dtype))


def _decay_mass(a, live):
    """The mean decay over a token's channels, summed over `live`
    tokens: a [n, H, d], live [n]."""
    return jnp.where(live, jnp.exp(a).mean((1, 2)), 0.0).sum()


def _kda_chunk(lp, x, i, cache, start, slot, valid, kc,
               cfg: BailingHybridConfig):
    T = x.shape[0]
    qkv, g, beta, gate = _kda_in(lp, x, cfg)
    fresh = start == 0                                    # a row begins
    real = jnp.arange(T) < valid
    with jax.named_scope("kda_conv"):
        tail = jnp.where(fresh, 0, cache["conv"][i, slot]).reshape(
            cfg.conv_kernel - 1, -1)
        c, tail = kda.kda_conv(qkv, tail, lp["conv"], valid)
        conv = cache["conv"].at[i, slot].set(tail.reshape(-1))
    with jax.named_scope("kda_gate"):
        q, k, v, a = _kda_gate(lp, g, c, cfg)
        # a pad neither decays nor writes
        a = jnp.where(real[:, None, None], a, 0.0)
        beta = jnp.where(real[:, None], beta, 0.0)
    with jax.named_scope("kda_chunk"):
        S0 = jnp.where(fresh, 0.0, cache["kda"][i, slot])
        o, S = kda.kda_chunk(q, k, v, a, beta, S0, valid)
        state = cache["kda"].at[i, slot].set(S)
    # (a call with no real token, the engine's warm-up, counts nothing)
    walked = jnp.where(valid > 0, kda.chunk_tokens_walked(*q.shape, valid), 0)
    kc = [kc[0] + _decay_mass(a, real), kc[1] + valid, kc[2], kc[3],
          kc[4] + walked, kc[5] + valid]
    return _kda_out(lp, x, o, gate, cfg), dict(cache, kda=state,
                                               conv=conv), kc


def _kda_tick(lp, x, i, cache, pos, kc, cfg: BailingHybridConfig):
    qkv, g, beta, gate = _kda_in(lp, x, cfg)
    active = pos > 0
    with jax.named_scope("kda_conv"):
        c, tail = kda.kda_conv_step(qkv, cache["conv"][i], lp["conv"],
                                    active)
        conv = cache["conv"].at[i].set(tail)
    with jax.named_scope("kda_gate"):
        q, k, v, a = _kda_gate(lp, g, c, cfg)
    with jax.named_scope("kda_step"):
        o, state, touched = kda.kda_step(q, k, v, a, beta, cache["kda"], i,
                                         active)
    live = active.sum()
    kc = [kc[0] + _decay_mass(a, active), kc[1] + live, kc[2] + touched,
          kc[3] + live] + kc[4:]
    return _kda_out(lp, x, o, gate, cfg), dict(cache, kda=state,
                                               conv=conv), kc


# ---------------------------------------------------------------------------
# The MLA mixer: deepseek_v2's two paths over deepseek_v2's cached row,
# with this model's projection and gate


def _rope(x, positions, cfg: BailingHybridConfig):
    """x [n, ..., d] at positions [n]: INTERLEAVED pairs (2i, 2i + 1),
    theta `rope_theta`, no scaling, in float32."""
    half = x.shape[-1] // 2
    inv = cfg.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv[None]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (half, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def _mla_project(lp, x, positions, cfg: BailingHybridConfig):
    """x [n, D] at positions [n] -> q_nope [n, H, 128], rotated q_pe
    [n, H, 64], the normed latent [n, 512], the rotated shared key part
    [n, 64]: one full-rank query projection, no norm a head."""
    dt = cfg.dtype
    h = _rms(x, lp["ln1"], cfg)
    q = jnp.einsum("nd,dhk->nhk", h, lp["wq"].astype(dt))
    kva = jnp.einsum("nd,dr->nr", h, lp["wkv_a"].astype(dt))
    ckv = _rms(kva[:, :cfg.kv_lora_rank], lp["kv_norm"], cfg)
    kpe = _rope(kva[:, cfg.kv_lora_rank:], positions, cfg)
    return (q[..., :cfg.qk_nope_head_dim],
            _rope(q[..., cfg.qk_nope_head_dim:], positions, cfg), ckv, kpe)


def _mla_gate(lp, x, cfg: BailingHybridConfig):
    """One sigmoid gate a token and head [n, H], float32."""
    return jax.nn.sigmoid(jnp.einsum(
        "nd,dh->nh", _rms(x, lp["ln1"], cfg), lp["wg"].astype(cfg.dtype),
        preferred_element_type=jnp.float32))


# ---------------------------------------------------------------------------
# The paged step


def _through_layers(params, x, cache, live, is_tick, mla, kda_mixer, cfg):
    counts = [jnp.int32(0)] * len(COUNTERS)
    kc = [jnp.float32(0)] + [jnp.int32(0)] * (len(KDA_COUNTERS) - 1)
    seen = {KDA: 0, MLA: 0}
    for lp, kind in zip(params["layers"], cfg.kinds):
        i = seen[kind]
        seen[kind] += 1
        if kind == MLA:
            x, cache = mla(lp, x, i, cache)
        else:
            x, cache, kc = kda_mixer(lp, x, i, cache, kc)
        x, counts = _ffn(lp, x, live, is_tick, counts, cfg)
    with jax.named_scope("lm_head"):
        x = _rms(x, params["ln_f"], cfg)
        logits = jnp.einsum("nd,dv->nv", x.astype(cfg.dtype),
                            params["wlm"].astype(cfg.dtype),
                            preferred_element_type=jnp.float32)
    kc = [jnp.round(kc[0] * _UNIT)] + kc[1:]
    return logits, dict(cache, moe=_ds._count(cache["moe"], counts),
                        kdac=_ds._count(cache["kdac"], kc))


def paged_chunk_step(params: Dict, tokens, pos, cache: Dict, block_tables,
                     cfg: BailingHybridConfig, pad_lo=None, slot=None,
                     valid=None) -> Tuple[Any, Dict]:
    """The model's paged step, under decode.paged_chunk_step's contract.

    `pos` a scalar: ONE row's chunk of T tokens starting there (T and
    `pos` whole pages) — single-row prefill.  It fills the row's latent
    pages of the MLA layers (the EXPANDED attention) and carries the
    delta-rule state and convolution tail of decode row `slot` (default
    0), zeroing both first when `pos` is 0; only the first `valid`
    tokens (default all) move them and are routed to experts.  `pos` a
    [B] vector with one token a row: the decode tick (the ABSORBED
    attention, the step kernel).  Rows at position 0 are idle: their
    page writes land wherever their block table points (the trash page),
    their state and tail stay exactly as they are and they are routed
    nowhere.
    Returns (logits [B, t, V] float32, cache)."""
    if pad_lo is not None:
        raise NotImplementedError("left-padded rows")
    B, t = tokens.shape
    psz = cache["lat"].shape[2]
    pos = jnp.asarray(pos, jnp.int32)
    embed = lambda tok: jnp.take(params["wte"], tok, axis=0  # noqa: E731
                                 ).astype(cfg.dtype)
    gate = lambda lp, x: _mla_gate(lp, x, cfg)               # noqa: E731
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
            lambda lp, x, i, c: _ds._attn_chunk(
                lp, x, i, c, bt, pos, cfg, project=_mla_project, gate=gate),
            lambda lp, x, i, c, kc: _kda_chunk(lp, x, i, c, pos, slot, valid,
                                               kc, cfg), cfg)
        return logits[None], cache
    if t != 1:
        raise NotImplementedError(
            "several tokens a row at per-row positions (speculative "
            "verify) need the recurrent state rolled back on rejection")
    logits, cache = _through_layers(
        params, embed(tokens[:, 0]), cache, pos > 0, True,
        lambda lp, x, i, c: _ds._attn_tick(
            lp, x, i, c, block_tables, pos, cfg, project=_mla_project,
            gate=gate),
        lambda lp, x, i, c, kc: _kda_tick(lp, x, i, c, pos, kc, cfg), cfg)
    return logits[:, None], cache


BODY = PagedBody(
    init_paged_cache=init_paged_cache, paged_chunk_step=paged_chunk_step,
    check_paging=check_paging, attn_keys=attn_keys, page_keys=("lat",),
    row_state_keys=("kda", "conv"), n_attn=lambda cfg: cfg.n_mla,
    attn_keys_gathered=attn_keys_gathered, attn_keys_paged=attn_keys_paged,
    snapshot_counters=snapshot_counters, read_counters=read_counters)
