"""K-EXAONE (`exaone_moe`): a GQA decoder whose attention layers are of
two kinds in a fixed pattern — WINDOW layers that attend to the last
`window` tokens (three in four, with RoPE) and GLOBAL layers that attend
to everything (one in four, without positions) — over a leading dense
SwiGLU layer and then layers of one shared + routed SwiGLU experts with
a sigmoid router that renormalises its top-k.  This module is the model
as the serving engine runs it: a config object, seeded weights, the
cache it declares, and its own paged step for a prefill chunk and for a
decode tick, bound into one declared body (`BODY`, a decode.PagedBody)
that the config names, so the engine's two jitted programs
(`engine._prefill_chunk`, `engine._paged_tick`) run it as they run every
model.

The cache (one pytree, `engine._cache`) holds two kinds of attention
state:

  k, v    [n_global, P, page, G, Dh]   pages of the GLOBAL layers only:
                                       they grow with the sequence, and
                                       they are all the pool and the
                                       engine's reservation count
  wk, wv  [n_window, B, W, G, Dh]      a RING per decode row and window
                                       layer: the token at position p is
                                       kept at p mod W (keys already
                                       rotated), so a row holds its last
                                       W = `window` tokens and nothing
                                       else, whatever its context
  moe     [7, 2] int32                 the expert layers' counters
                                       (deepseek_v2.COUNTERS)

A ring is state per decode row (`row_state_keys`): what treats a page as the
whole of a sequence's state (prefix cache, tiers, kv_export / kv_import,
migration, session checkpoints) refuses this model by name
(kv_tier.refuse_row_state).  Nothing zeroes a ring when a row changes
hands: entry i of a row at position p holds position p - ((p - i) mod W),
and an entry whose position would be negative is masked, so what an
earlier sequence left is never read.

A tick reads, in a window layer, the row's ring and nothing else (W keys
a row); a prefill chunk of T tokens reads the W ring entries before it
(the oldest is out of every query's window: W - 1 visible) beside its
own T keys, in blocks of W queries against 2 W keys, and leaves its last
W real tokens in the ring.  A global layer's chunk walks the row's pages
in spans, as the dense body of models/decode.py does; its tick reads, on
a TPU, each row's own pages through the kernel of ops/paged_attention.py
and, where there is none, spans to the deepest row's depth for every row
(`_span_tick`).

The expert layer is told which experts it holds (`experts_held`,
`expert_offset`): the router scores ALL `n_routed_experts` with a
sigmoid, the top-k are chosen among all of them, their weights are
renormalised over the k chosen WHEREVER THEY LIVE and scaled, and the
layer computes `shared(x) + sum over (top-k AND held) of w_i expert_i(x)`
through `deepseek_v2.routed_experts` (the Pallas grouped matmul) and its
device counters.  What the absent experts would add is left out.

What the published config does not pin, and what was taken (the
benchmark's configuration file argues each): pre-norm blocks; an RMSNorm
over each head of q and k; RoPE (pairs (i, i + Dh/2)) in window layers
only.  The multi-token-prediction layer is not here.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.models import deepseek_v2 as _ds
from ray_tpu.models.decode import PagedBody, _rope_at, _swiglu
from ray_tpu.ops import paged_attention as _pa

_HI = lax.Precision.HIGHEST
# Keys one span of a GLOBAL layer's attention covers (whole pages).  A
# tick WITHOUT A TPU gathers a span's pages for every row of the call
# (on one, `ops/paged_attention.py` walks each row's own pages); a chunk
# scores all its queries against a span in float32, [heads, queries,
# keys].
_TICK_SPAN_KEYS = 256
_CHUNK_SPAN_KEYS = 256

COUNTERS = _ds.COUNTERS
# A sink's share of a head's softmax is counted in units of 2^-10, so
# that the counters stay whole numbers (deepseek_v2._count).
_SINK_UNIT = 1 << 10
# whole pages a span of so many keys covers; one span merged into a
# running softmax (maxima, sums, accumulator)
_span_pages = _ds._span_pages
_merge = _ds._merge


@dataclasses.dataclass(frozen=True)
class AttnKind:
    """One kind of attention layer as the attention functions below read
    it, so that a model whose two kinds differ in more than the window
    (models/mimo_v2_flash.py: other head counts, keys wider than values,
    a theta each, a sink) runs through the same functions.  The defaults
    are K-EXAONE's."""
    n_kv_heads: int
    head_dim: int                         # a query's and a key's width
    v_head_dim: int                       # a value's
    window: int = 0                       # 0: attends to all (pages)
    rope_theta: Optional[float] = None    # None: no positions
    rotary_dim: Optional[int] = None      # None: the whole head
    qk_norm: bool = True
    v_scale: float = 1.0
    sink: bool = False    # a learned score per head in the denominator
    flat: bool = False    # the cache keeps a token's heads side by side


def _kept(kind: AttnKind, width: int) -> Tuple[int, ...]:
    """The trailing shape a cache array keeps a token's heads of `width`
    in: [heads, width] (K-EXAONE's [8, 128]) or, for a kind that says
    `flat`, the heads side by side, [heads x width]: the same numbers in
    the same order.  A kind says so whose keys are no whole number of
    the chip's 128 lanes wide (MiMo's 4 x 192 = 768, and its values with
    them): the chip's compiler gives an array that ends in [4, 192] a
    layout of its own with the PAGES innermost, and a step then re-lays
    the whole pool on its way in and out (2 GiB of temporaries a tick at
    mimo-v2-flash-ep16-d7's sizes; tests/test_tpu_compile.py holds that
    it does not).  A reader reshapes what it gathered, never the pool."""
    if kind.flat:
        return (kind.n_kv_heads * width,)
    return (kind.n_kv_heads, width)


@dataclasses.dataclass(frozen=True)
class ExaoneMoeConfig:
    """Published K-EXAONE-236B-A23B sizes by default; `experts_held`,
    `expert_offset`, `vocab_size` and `n_layers` say the share this chip
    holds.  `sliding_windows` is the published per-layer list (0: a
    global layer), of which the first `n_layers` entries are run.
    Hashable: the engine passes it as a static argument."""
    max_seq: int
    n_layers: int = 48
    vocab_size: int = 153600
    d_model: int = 6144
    n_heads: int = 64
    n_kv_heads: int = 8
    head_dim: int = 128
    d_ff: int = 18432                 # the leading dense layers
    first_k_dense: int = 1
    moe_d_ff: int = 2048
    n_routed_experts: int = 128       # what the router scores: never cut
    n_shared_experts: int = 1
    top_k: int = 8
    routed_scaling_factor: float = 2.5
    experts_held: Optional[int] = None    # None: all of them
    expert_offset: int = 0
    sliding_windows: Tuple[int, ...] = (128, 128, 128, 0) * 12
    rope_theta: float = 1e6
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.experts_held is None:
            object.__setattr__(self, "experts_held", self.n_routed_experts)
        object.__setattr__(self, "sliding_windows",
                           tuple(self.sliding_windows)[:self.n_layers])
        if len(self.sliding_windows) != self.n_layers:
            raise ValueError("sliding_windows must name every layer run")
        if len(set(self.sliding_windows) - {0}) > 1:
            raise ValueError("the window layers share one window")
        if self.top_k > self.n_routed_experts:
            raise ValueError("top_k exceeds the routed experts")
        if self.expert_offset < 0 or self.experts_held < 1 \
                or self.expert_offset + self.experts_held \
                > self.n_routed_experts:
            raise ValueError("the held experts must lie among the routed")
        if not 0 <= self.first_k_dense <= self.n_layers:
            raise ValueError("first_k_dense must be 0..n_layers")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")

    @property
    def window(self) -> int:
        """Tokens a window layer attends to and a ring holds (0: the
        model has no window layer)."""
        return max(self.sliding_windows, default=0)

    @property
    def n_window(self) -> int:
        return sum(w > 0 for w in self.sliding_windows)

    @property
    def n_global(self) -> int:
        return self.n_layers - self.n_window

    @property
    def n_moe(self) -> int:
        return self.n_layers - self.first_k_dense

    def kind(self, windowed: bool) -> AttnKind:
        """Both kinds share heads and widths; a window layer rotates."""
        return AttnKind(self.n_kv_heads, self.head_dim, self.head_dim,
                        window=self.window if windowed else 0,
                        rope_theta=self.rope_theta if windowed else None)

    @property
    def paged_body(self) -> PagedBody:
        return BODY


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _kind_index(cfg: ExaoneMoeConfig, l: int) -> Tuple[bool, int]:
    """(is layer `l` a window layer, its index among layers of its
    kind)."""
    windowed = cfg.sliding_windows[l] > 0
    return windowed, sum((w > 0) == windowed
                         for w in cfg.sliding_windows[:l])


def attn_keys(cfg: ExaoneMoeConfig, pos: np.ndarray) -> Tuple[int, int]:
    """(keys read, keys held) by one tick's decode rows at positions
    `pos`, summed over rows and layers: a global layer holds and reads
    all `pos + 1`, a window layer `min(pos + 1, window)`: the tick reads
    all a row holds, and a row holds less than its context."""
    pos = np.asarray(pos, np.int64)
    held = int((pos + 1).sum()) * cfg.n_global \
        + int(np.minimum(pos + 1, cfg.window).sum()) * cfg.n_window
    return held, held


def attn_keys_paged(cfg: ExaoneMoeConfig, pos: np.ndarray,
                    all_pos: np.ndarray, page_size: int, nblk: int
                    ) -> Tuple[int, int]:
    """(keys gathered, keys held) in the GLOBAL layers alone by one tick
    whose active rows stand at `pos`: the layers whose keys live in
    pages.  Gathered, for EVERY row of the call (`all_pos` of all decode
    rows, idle ones at 0): on a TPU each row's own blocks of pages, what
    the kernel copies; elsewhere whole spans up to the deepest row's
    token, the trip count the span loop reads from the positions."""
    held = int((np.asarray(pos, np.int64) + 1).sum()) * cfg.n_global
    if _on_tpu():
        kind = cfg.kind(False)
        token = kind.n_kv_heads * (kind.head_dim + kind.v_head_dim) \
            * jnp.dtype(cfg.dtype).itemsize
        return _pa.keys_copied(all_pos, page_size, nblk, token) \
            * cfg.n_global, held
    cols = _span_pages(_TICK_SPAN_KEYS, page_size, nblk) * page_size
    spans = -(-(int(np.asarray(all_pos).max()) + 1) // cols)
    return len(all_pos) * spans * cols * cfg.n_global, held


def attn_keys_gathered(cfg: ExaoneMoeConfig, pos: np.ndarray,
                       page_size: int, nblk: int) -> int:
    """Keys one tick pulls from the cache (`pos` of all decode rows): in
    a global layer what attn_keys_paged says; in a window layer every
    row's whole ring."""
    return attn_keys_paged(cfg, pos, pos, page_size, nblk)[0] \
        + len(pos) * cfg.window * cfg.n_window


def check_paging(cfg: ExaoneMoeConfig, *, page_size: int,
                 prefill_chunk: int, speculate_k: int) -> None:
    if prefill_chunk % page_size:
        raise ValueError(f"a prefill chunk writes whole pages of the "
                         f"global layers: prefill_chunk must be a multiple "
                         f"of page_size={page_size}, got {prefill_chunk}")
    if speculate_k:
        raise NotImplementedError(
            "speculative verify on a model with per-row window rings "
            "needs the ring rolled back to the accepted token")


# ---------------------------------------------------------------------------
# Weights and cache


def seeded_draws(cfg, key, dtype, per_layer: int = 16):
    """What a model of this family draws its seeded weights with, at
    most `per_layer` arrays a layer:
    (`nrm(shape, scale, dt=dtype)`, a normal in float32 cast to `dt`,
    each call a key of its own in the order of the calls; `swiglu(width,
    *lead)`, three of them, std `s` in and `so` out; `s` 0.02; `so`
    0.02 / sqrt(2 n_layers), for projections back into the residual
    stream)."""
    D = cfg.d_model
    s = 0.02
    so = s / np.sqrt(2 * cfg.n_layers)
    keys = iter(jax.random.split(key, 2 + per_layer * cfg.n_layers))

    def nrm(shape, scale, dt=dtype):
        return (scale * jax.random.normal(next(keys), shape, jnp.float32)
                ).astype(dt)

    def swiglu(width, *lead):
        return {"w_gate": nrm(lead + (D, width), s),
                "w_up": nrm(lead + (D, width), s),
                "w_down": nrm(lead + (width, D), so)}
    return nrm, swiglu, s, so


def init_params(cfg: ExaoneMoeConfig, key, dtype=None) -> Dict:
    """Seeded weights, one dict a layer (normal, std 0.02; projections
    back into the residual stream 0.02 / sqrt(2 n_layers); the router in
    float32, as it is applied, and its selection bias zero)."""
    dtype = dtype or cfg.dtype
    D, H, G, Dh, F = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                      cfg.head_dim, cfg.moe_d_ff)
    nrm, swiglu, s, so = seeded_draws(cfg, key, dtype)
    ones = lambda *shape: jnp.ones(shape, jnp.float32)  # noqa: E731

    def layer(i):
        lp = {"ln1": ones(D), "wq": nrm((D, H, Dh), s),
              "wkv": nrm((D, 2, G, Dh), s), "qn": ones(Dh), "kn": ones(Dh),
              "wo": nrm((H, Dh, D), so), "ln2": ones(D)}
        if i < cfg.first_k_dense:
            return dict(lp, **swiglu(cfg.d_ff))
        return dict(lp, router=nrm((D, cfg.n_routed_experts), s,
                                   jnp.float32),
                    router_bias=jnp.zeros((cfg.n_routed_experts,),
                                          jnp.float32),
                    shared=swiglu(cfg.n_shared_experts * F),
                    experts=swiglu(F, cfg.experts_held))

    return {"wte": nrm((cfg.vocab_size, D), s),
            "layers": tuple(layer(i) for i in range(cfg.n_layers)),
            "ln_f": ones(D), "wlm": nrm((D, cfg.vocab_size), s)}


def init_paged_cache(cfg: ExaoneMoeConfig, num_pages: int, page_size: int,
                     num_slots: Optional[int] = None) -> Dict:
    def pair(lead, kind):
        return (jnp.zeros(lead + _kept(kind, kind.head_dim), cfg.dtype),
                jnp.zeros(lead + _kept(kind, kind.v_head_dim), cfg.dtype))
    windowed = cfg.kind(True)
    k, v = pair((cfg.n_global, num_pages, page_size), cfg.kind(False))
    wk, wv = pair((cfg.n_window, num_slots or 1, cfg.window), windowed)
    cache = {"k": k, "v": v, "wk": wk, "wv": wv,
             "moe": jnp.zeros((len(COUNTERS), 2), jnp.int32)}
    if windowed.sink:
        # [share of the softmax the sinks took, in _SINK_UNIT; the
        # (token, head, window layer) softmaxes counted], as `moe`
        cache["sink"] = jnp.zeros((2, 2), jnp.int32)
    return cache


def snapshot_counters(cache: Dict) -> Dict:
    """deepseek_v2.snapshot_counters, with the sinks' two beside the
    expert layers' where the model has them."""
    snap = _ds.snapshot_counters(cache)
    if "sink" in cache:
        snap["sink"] = jnp.copy(cache["sink"])
        snap["sink"].copy_to_host_async()
    return snap


def read_counters(cache: Dict, cfg) -> Dict[str, Any]:
    """The expert layers' counters (deepseek_v2.read_counters) and,
    where window softmaxes have a sink, `attn_sink_mass`: the share of
    its softmax a sink took, summed over ticks' live rows, chunks' real
    tokens, heads and window layers, and `attn_sink_softmaxes`: how many
    softmaxes that sums.  Their ratio is the mean share a sink takes."""
    counts = _ds.read_counters(cache, cfg)
    if "sink" in cache:
        mass, heads = (int((hi << _ds._WORD) + lo) for hi, lo in
                       np.asarray(cache["sink"]).astype(np.int64))
        counts["attn_sink_mass"] = mass / _SINK_UNIT
        counts["attn_sink_softmaxes"] = heads
    return counts


def _rms(x, scale, cfg: ExaoneMoeConfig):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + cfg.rms_eps) * scale).astype(x.dtype)


# ---------------------------------------------------------------------------
# The expert layer


def route(router, bias, h, cfg: ExaoneMoeConfig):
    """Sigmoid scores over ALL routed experts, in float32.  h [N, D] ->
    (expert ids [N, top_k], weights [N, top_k] float32): the top_k
    largest of score + bias are chosen; a chosen expert's weight is its
    score over the sum of the chosen scores (wherever those experts
    live), times `routed_scaling_factor`."""
    logits = jnp.einsum("nd,de->ne", h.astype(jnp.float32),
                        router.astype(jnp.float32), precision=_HI)
    s = jax.nn.sigmoid(logits)
    ids = lax.top_k(s + bias[None], cfg.top_k)[1]
    w = jnp.take_along_axis(s, ids, axis=1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * cfg.routed_scaling_factor
    return ids.astype(jnp.int32), w


def _ffn(lp, x, live, is_tick, counts, cfg: ExaoneMoeConfig):
    """x + FFN(norm(x)): dense SwiGLU in the leading layers, the shared
    expert (where the layer has one) + held routed experts after them.
    `counts`: this call's additions to COUNTERS so far."""
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
    if "shared" in lp:
        routed = routed + _swiglu(lp["shared"], h, dt)
    return x + routed.astype(x.dtype), counts


# ---------------------------------------------------------------------------
# Attention, for a single-row chunk of T tokens (x [T, D]) and for a tick
# of B rows (x [B, D]).  `i` indexes the layer among the layers of its
# kind (its pages, or its rings); `kind` is that kind's AttnKind.


def _rotate(x, positions, kind: AttnKind):
    """RoPE over the first `rotary_dim` of a head (all of it by
    default), pairs (i, i + rotary_dim / 2); the rest passes."""
    rd = kind.rotary_dim
    if rd is None or rd == x.shape[-1]:
        return _rope_at(x[None], positions[None], kind.rope_theta)[0]
    turned = _rope_at(x[None, ..., :rd], positions[None], kind.rope_theta)[0]
    return jnp.concatenate([turned, x[..., rd:]], axis=-1)


def _project(lp, x, positions, kind: AttnKind, cfg):
    """x [n, D] at positions [n] -> q [n, H, Dh], k [n, G, Dh], v
    [n, G, Dv]: q and k normed per head where the kind norms them,
    rotated where it has positions, v scaled where it has a scale.  The
    k and v projections are one array `wkv` where they are as wide, or
    `wk` and `wv`."""
    dt = cfg.dtype
    h = _rms(x, lp["ln1"], cfg)
    q = jnp.einsum("nd,dhk->nhk", h, lp["wq"].astype(dt))
    if "wkv" in lp:
        kv = jnp.einsum("nd,dchk->nchk", h, lp["wkv"].astype(dt))
        k, v = kv[:, 0], kv[:, 1]
    else:
        k = jnp.einsum("nd,dhk->nhk", h, lp["wk"].astype(dt))
        v = jnp.einsum("nd,dhk->nhk", h, lp["wv"].astype(dt))
    if kind.qk_norm:
        q, k = _rms(q, lp["qn"], cfg), _rms(k, lp["kn"], cfg)
    if kind.rope_theta is not None:
        q, k = _rotate(q, positions, kind), _rotate(k, positions, kind)
    if kind.v_scale != 1.0:
        v = v * kind.v_scale
    return q, k, v


def _close(lp, x, out, cfg):
    return x + jnp.einsum("nhk,hkd->nd", out, lp["wo"].astype(cfg.dtype))


def _global_chunk(lp, x, i, cache, bt, start, kind: AttnKind, cfg,
                  last=None):
    T = x.shape[0]
    G, Dh, Dv = kind.n_kv_heads, kind.head_dim, kind.v_head_dim
    psz = cache["k"].shape[2]
    q, k, v = _project(lp, x, start + jnp.arange(T), kind, cfg)
    pages = lax.dynamic_slice(bt, (start // psz,), (T // psz,))
    paged = (T // psz, psz)
    ck = cache["k"].at[i, pages].set(k.reshape(paged + _kept(kind, Dh)))
    cv = cache["v"].at[i, pages].set(v.reshape(paged + _kept(kind, Dv)))
    with jax.named_scope("attn_global"):
        out = _span_chunk(q, ck, cv, i, bt, start, kind, last)
    return _close(lp, x, out, cfg), dict(cache, k=ck, v=cv)


def _span_chunk(q, ck, cv, i, bt, start, kind: AttnKind, last=None):
    """A single-row chunk's attention over the pages of layer `i` of the
    pools ck, cv [n, P, page, ...], the chunk's own keys among them
    (written before the call): q [T, H, Dh] at positions start.. over
    the row's pages `bt` in spans of `_CHUNK_SPAN_KEYS` keys, each
    query up to its own position -> [T, H, Dv]; or up to `last` [T],
    a column inside the chunk each, where a model's mask is not causal
    (models/sdar_moe.py: through the end of the query's block).  Also
    models/zaya.py's (pages of two heads side by side)."""
    T, H, _ = q.shape
    G, Dh, Dv = kind.n_kv_heads, kind.head_dim, kind.v_head_dim
    R = H // G
    psz, nblk = ck.shape[2], bt.shape[0]
    dt = q.dtype
    cols = start + jnp.arange(T) if last is None else last
    span = _span_pages(_CHUNK_SPAN_KEYS, psz, nblk)
    width = span * psz
    qg = q.reshape(T, G, R, Dh)

    def attend(j, part):
        first = jnp.minimum(j * span, nblk - span)   # as the slice clamps
        pg = lax.dynamic_slice(bt, (first,), (span,))
        ks = ck[i, pg].reshape(width, G, Dh)
        vs = cv[i, pg].reshape(width, G, Dv)
        s = jnp.einsum("tgrd,sgd->grts", qg, ks,
                       preferred_element_type=jnp.float32) * Dh ** -0.5
        kcols = first * psz + jnp.arange(width)
        seen = (kcols[None, :] <= cols[:, None]) \
            & (kcols[None, :] >= j * width)
        s = jnp.where(seen[None, None], s, -jnp.inf)
        return _merge(part, s, lambda e: jnp.einsum(
            "grts,sgd->grtd", e.astype(dt), vs,
            preferred_element_type=jnp.float32))

    stat = jnp.full((G, R, T), -jnp.inf, jnp.float32)
    _, total, acc = lax.fori_loop(
        0, (start + T + width - 1) // width, attend,
        (stat, jnp.zeros_like(stat),
         jnp.zeros((G, R, T, Dv), jnp.float32)))
    out = (acc / total[..., None]).astype(dt)             # [G, R, T, Dv]
    return jnp.moveaxis(out, 2, 0).reshape(T, G * R, Dv)


def _global_tick(lp, x, i, cache, bt, pos, kind: AttnKind, cfg):
    B = x.shape[0]
    G, Dh, Dv = kind.n_kv_heads, kind.head_dim, kind.v_head_dim
    psz = cache["k"].shape[2]
    q, k, v = _project(lp, x, pos, kind, cfg)
    page = jnp.take_along_axis(bt, (pos // psz)[:, None], axis=1)[:, 0]
    ck = cache["k"].at[i, page, pos % psz].set(
        k.reshape((B,) + _kept(kind, Dh)))
    cv = cache["v"].at[i, page, pos % psz].set(
        v.reshape((B,) + _kept(kind, Dv)))

    with jax.named_scope("attn_global"):
        if _on_tpu():
            out = _pa.paged_attention(q, ck, cv, i, bt, pos, n_kv_heads=G)
        else:
            out = _span_tick(q, ck, cv, i, bt, pos, kind)
    return _close(lp, x, out, cfg), dict(cache, k=ck, v=cv)


def _span_tick(q, ck, cv, i, bt, pos, kind: AttnKind):
    """The tick's attention over the pages of layer `i` of the pools ck,
    cv [n, P, page, ...] where there is no TPU, and what the kernel is
    held equal to: a loop whose trip count is the DEEPEST row's depth
    gathers, for every row of the call, a span of `_TICK_SPAN_KEYS` keys
    a trip and masks what a shallower row does not hold.  q [B, H, Dh]
    -> [B, H, Dv]."""
    B, H, _ = q.shape
    G, Dh, Dv = kind.n_kv_heads, kind.head_dim, kind.v_head_dim
    R = H // G
    psz, nblk = ck.shape[2], bt.shape[1]
    dt = q.dtype
    span = _span_pages(_TICK_SPAN_KEYS, psz, nblk)
    width = span * psz
    kept = _kept(kind, Dh)
    if kind.flat:
        # Keys whose heads lie side by side are scored as they lie: a
        # head's query stands in its own head's lanes of a row as wide
        # as all of them, zeros elsewhere.  G x the multiplies, in a
        # call the gathered bytes bound; re-laying every gathered span
        # to [G, Dh] instead made a 64-row tick 37.5 ms, not 25.2
        # (PERF.md section 6, PR 51).
        qg = _pa.widen(q, G).reshape((B, G, R) + kept)
    else:
        qg = q.reshape(B, G, R, Dh)
    scores = "bgrd,bsd->bgrs" if kind.flat else "bgrd,bsgd->bgrs"

    def attend(j, part):
        first = jnp.minimum(j * span, nblk - span)
        pg = lax.dynamic_slice(bt, (0, first), (B, span))
        ks = ck[i, pg].reshape((B, width) + kept)
        vs = cv[i, pg].reshape(B, width, G, Dv)
        s = jnp.einsum(scores, qg, ks,
                       preferred_element_type=jnp.float32) * Dh ** -0.5
        kcols = first * psz + jnp.arange(width)
        seen = (kcols[None, :] <= pos[:, None]) \
            & (kcols[None, :] >= j * width)
        s = jnp.where(seen[:, None, None], s, -jnp.inf)
        return _merge(part, s, lambda e: jnp.einsum(
            "bgrs,bsgd->bgrd", e.astype(dt), vs,
            preferred_element_type=jnp.float32))

    stat = jnp.full((B, G, R), -jnp.inf, jnp.float32)
    _, total, acc = lax.fori_loop(
        0, (jnp.max(pos) + width) // width, attend,
        (stat, jnp.zeros_like(stat),
         jnp.zeros((B, G, R, Dv), jnp.float32)))
    return (acc / total[..., None]).astype(dt).reshape(B, H, Dv)


def _window_attend(qg, k, v, qpos, kpos, W, dt, sink=None):
    """qg [..., n, G, R, Dh] at positions qpos [..., n] over keys k
    [..., s, G, Dh], values v [..., s, G, Dv] at positions kpos [..., s]
    (negative: nothing is there): key s is visible to query t iff
    0 <= t - s < W.  `sink` [G, R] float32, where the kind has one: a
    learned score per head that joins the softmax's denominator and has
    no value, so a head's weights sum to less than 1.  Returns (out
    [..., n, G, R, Dv], the share of each softmax its sink took
    [..., G, R, n]; None without a sink)."""
    s = jnp.einsum("...ngrd,...sgd->...grns", qg, k,
                   preferred_element_type=jnp.float32) * qg.shape[-1] ** -0.5
    back = qpos[..., :, None] - kpos[..., None, :]
    seen = (back >= 0) & (back < W) & (kpos[..., None, :] >= 0)
    s = jnp.where(seen[..., None, None, :, :], s, -jnp.inf)
    if sink is None:
        p, share = jax.nn.softmax(s, axis=-1), None
    else:
        sink = sink[:, :, None]                              # [G, R, 1]
        top = jnp.maximum(s.max(-1), sink)
        e = jnp.exp(s - top[..., None])
        drain = jnp.exp(sink - top)
        total = e.sum(-1) + drain
        p, share = e / total[..., None], drain / total
    return jnp.einsum("...grns,...sgd->...ngrd", p.astype(dt), v), share


def _count_sinks(cache, share, live, cfg):
    """Add one window layer's sinks to the cache's counters: `share`
    [..., G, R, n] with `live` [..., n] the queries that count."""
    if share is None:
        return cache
    mass = (share * live[..., None, None, :]).sum()
    return dict(cache, sink=_ds._count(cache["sink"], [
        jnp.round(mass * _SINK_UNIT), live.sum() * cfg.n_heads]))


def _window_chunk(lp, x, i, cache, start, slot, valid, kind: AttnKind, cfg):
    T = x.shape[0]
    G, Dh, Dv, W = (kind.n_kv_heads, kind.head_dim, kind.v_head_dim,
                    kind.window)
    R = cfg.n_heads // G
    dt = cfg.dtype
    cols = start + jnp.arange(T)
    q, k, v = _project(lp, x, cols, kind, cfg)
    sink = lp["sink"].reshape(G, R) if kind.sink else None

    with jax.named_scope("attn_window"):
        # the W tokens before the chunk, in position order
        before = (start + jnp.arange(W)) % W
        kall = jnp.concatenate(
            [cache["wk"][i, slot].reshape(W, G, Dh)[before], k])
        vall = jnp.concatenate(
            [cache["wv"][i, slot].reshape(W, G, Dv)[before], v])
        kpos = start - W + jnp.arange(W + T)
        qb = W if T % W == 0 else T          # queries a block
        qg = q.reshape(T // qb, qb, G, R, Dh)

        def block(j):
            # block j's queries see the qb keys of their own block and
            # the W before it: kall[j qb : j qb + W + qb]
            take = lambda a: lax.dynamic_slice_in_dim(  # noqa: E731
                a, j * qb, W + qb)
            return _window_attend(qg[j], take(kall), take(vall),
                                  start + j * qb + jnp.arange(qb),
                                  take(kpos), W, dt, sink)
        out, share = lax.map(block, jnp.arange(T // qb))
        out = out.reshape(T, G * R, Dv)

        # the ring after the chunk: entry e holds the last REAL position
        # congruent to e, from the chunk where that lies inside it
        last = start + valid - 1
        at = last - (last - jnp.arange(W)) % W
        src = jnp.clip(at - start, 0, T - 1)
        # (an entry the chunk does not reach is written nowhere)
        entries = jnp.where(at >= start, jnp.arange(W), W)
        wk = cache["wk"].at[i, slot, entries].set(
            k[src].reshape((W,) + _kept(kind, Dh)), mode="drop")
        wv = cache["wv"].at[i, slot, entries].set(
            v[src].reshape((W,) + _kept(kind, Dv)), mode="drop")
        cache = _count_sinks(dict(cache, wk=wk, wv=wv), share,
                             (jnp.arange(T) < valid).reshape(T // qb, qb),
                             cfg)
    return _close(lp, x, out, cfg), cache


def _window_tick(lp, x, i, cache, pos, kind: AttnKind, cfg):
    B = x.shape[0]
    G, Dh, Dv, W = (kind.n_kv_heads, kind.head_dim, kind.v_head_dim,
                    kind.window)
    R = cfg.n_heads // G
    q, k, v = _project(lp, x, pos, kind, cfg)
    sink = lp["sink"].reshape(G, R) if kind.sink else None

    with jax.named_scope("attn_window"):
        # a row at position 0 is idle, or the row a prefill is filling:
        # its ring stays as it is
        at = jnp.where(pos > 0, pos % W, W)
        rows = jnp.arange(B)
        wk = cache["wk"].at[i, rows, at].set(
            k.reshape((B,) + _kept(kind, Dh)), mode="drop")
        wv = cache["wv"].at[i, rows, at].set(
            v.reshape((B,) + _kept(kind, Dv)), mode="drop")
        entries = jnp.arange(W)[None, :]
        kpos = pos[:, None] - (pos[:, None] - entries) % W
        out, share = _window_attend(
            q.reshape(B, 1, G, R, Dh), wk[i].reshape(B, W, G, Dh),
            wv[i].reshape(B, W, G, Dv), pos[:, None], kpos, W, cfg.dtype,
            sink)
        cache = _count_sinks(dict(cache, wk=wk, wv=wv), share,
                             (pos > 0)[:, None], cfg)
    return _close(lp, x, out.reshape(B, G * R, Dv), cfg), cache


# ---------------------------------------------------------------------------
# The paged step


def _through_layers(params, x, cache, live, is_tick, attend, cfg):
    counts = [jnp.int32(0)] * len(COUNTERS)
    for l, lp in enumerate(params["layers"]):
        x, cache = attend(lp, x, *_kind_index(cfg, l), cache)
        x, counts = _ffn(lp, x, live, is_tick, counts, cfg)
    x = _rms(x, params["ln_f"], cfg)
    logits = jnp.einsum("nd,dv->nv", x.astype(cfg.dtype),
                        params["wlm"].astype(cfg.dtype),
                        preferred_element_type=jnp.float32)
    return logits, dict(cache, moe=_ds._count(cache["moe"], counts))


def paged_chunk_step(params: Dict, tokens, pos, cache: Dict, block_tables,
                     cfg, pad_lo=None, slot=None,
                     valid=None) -> Tuple[Any, Dict]:
    """The model's paged step, under decode.paged_chunk_step's contract
    (`cfg`: an ExaoneMoeConfig, or any config that answers `kind`,
    `sliding_windows` and the expert layer's fields as one does).

    `pos` a scalar: ONE row's chunk of T tokens starting there (T and
    `pos` whole pages) — single-row prefill.  It fills the row's pages
    of the global layers and leaves the last `window` of its first
    `valid` tokens (default all) in the rings of decode row `slot`
    (default 0); only those tokens are routed to experts.  `pos` a [B]
    vector with one token a row: the decode tick.  Rows at position 0
    are idle: their page writes land wherever their block table points
    (the trash page), their rings are not written, and they are routed
    nowhere.
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

        def attend(lp, x, windowed, i, c):
            kind = cfg.kind(windowed)
            if windowed:
                return _window_chunk(lp, x, i, c, pos, slot, valid, kind, cfg)
            return _global_chunk(lp, x, i, c, bt, pos, kind, cfg)
        logits, cache = _through_layers(
            params, embed(tokens[0]), cache, jnp.arange(t) < valid, False,
            attend, cfg)
        return logits[None], cache
    if t != 1:
        raise NotImplementedError(
            "several tokens a row at per-row positions (speculative "
            "verify) need the window rings rolled back on rejection")

    def attend(lp, x, windowed, i, c):
        kind = cfg.kind(windowed)
        if windowed:
            return _window_tick(lp, x, i, c, pos, kind, cfg)
        return _global_tick(lp, x, i, c, block_tables, pos, kind, cfg)
    logits, cache = _through_layers(
        params, embed(tokens[:, 0]), cache, pos > 0, True, attend, cfg)
    return logits[:, None], cache


BODY = PagedBody(
    init_paged_cache=init_paged_cache, paged_chunk_step=paged_chunk_step,
    check_paging=check_paging, attn_keys=attn_keys,
    row_state_keys=("wk", "wv"), attn_keys_gathered=attn_keys_gathered,
    attn_keys_paged=attn_keys_paged, snapshot_counters=snapshot_counters,
    read_counters=read_counters)
