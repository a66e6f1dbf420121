"""DeepSeek-V2: multi-head latent attention (MLA) over a latent page, a
leading dense SwiGLU layer, then layers of shared + routed SwiGLU experts
with group-limited top-k routing that drops nothing.  This module is the
model as the serving engine runs it: a config object, seeded weights,
the cache it declares, and its own paged step for a prefill chunk and
for a decode tick, bound into one declared body (`BODY`, a
decode.PagedBody) that the config names, so the engine's two jitted
programs (`engine._prefill_chunk`, `engine._paged_tick`) run it as they
run every model.

The cache (one pytree, `engine._cache`):

  lat   [L, P, page, 640]   one row a token and layer: the compressed
                            latent (512, normed: all heads' keys and
                            values), the rotated key part every head
                            shares (64), and 64 zeros.  576 numbers are
                            what a token IS (1,152 B); the chip's tile is
                            128 lanes wide, so they occupy 640 (1,280 B)
                            whoever pads them.  Kept as two arrays the
                            64-wide one is padded to 128 by the compiler
                            (the same bytes), which then re-lays that
                            whole pool twice a tick (0.5 GB each, by its
                            own account for a v5e); one array is also one
                            gather a span, not two
  moe   [7, 2] int32                 the expert layers' own counters,
                                     cumulative (COUNTERS; two words
                                     each, so they do not wrap)

Two attention paths over that one cache.  A prefill chunk EXPANDS: it
forms k_nope and v of the context from the cached latents (`wk_b`,
`wv_b`, the two halves of the published kv_b_proj) span by span and
attends at head width 192 / 128.  A decode tick ABSORBS: `wk_b` goes
into the query and `wv_b` into the output, so attention runs in the
512 + 64 latent space and no key or value is ever formed.

The expert layer is told which experts it holds (`experts_held`,
`expert_offset`): the router scores ALL `n_routed_experts`, the top-k
are chosen among all of them, and the layer computes
`shared(x) + sum over (top-k AND held) of w_i expert_i(x)`.  What the
absent experts would add is left out; nothing stands in for them.
Routed pairs are sorted by expert, the held ones first, and those run
through one grouped matmul a projection (Pallas `megablox.gmm`) a slab:
where a share of the experts is held a slab is as many pairs as the call
has tokens, where all are held it is the whole call, in line.  The
layer's work follows the pairs held here, not the pairs routed; an
expert no token chose is not read, and one that is chosen is read once a
slab, in blocks of a whole contraction (`_tiles`).  `routed_experts`,
`count_routed` and the counters have a second caller, models/exaone_moe.py
(a sigmoid router of its own over the same grouped matmul): they read
`cfg.top_k`, `cfg.experts_held`, `cfg.n_routed_experts`,
`cfg.expert_offset` and, for the counter of reads, `cfg.d_model`,
`cfg.moe_d_ff` and `cfg.dtype`, and nothing else (it also borrows
`_span_pages` and `_merge`, which read no config).

Departures from the published modeling file, all relabellings under
seeded weights: RoPE pairs are (i, i + d/2), not interleaved; kv_b_proj
is kept as its halves `wk_b` [heads, 128, 512] / `wv_b` [heads, 512, 128],
head-major as the tick's per-head products read them (latent-major, the
tick copied both every layer); `q_b_proj` is [rank, heads * 192]
(heads major); the two shared experts are one SwiGLU of twice the width
(as published); layers are a tuple, not a stack, so each layer's expert
weights reach the grouped matmul as an array of their own.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental.pallas.ops.tpu.megablox import gmm

from ray_tpu.models.decode import PagedBody, _swiglu
from ray_tpu.models.gpt import _rmsnorm
from ray_tpu.ops import paged_attention as _pa

_HI = lax.Precision.HIGHEST
# Keys one span of attention covers (whole pages), chosen on a v5e at
# the benchmark's sizes (PERF.md section 6, PR 35).  A tick gathers a
# span's rows for every row of the call: 64 rows ~3.6k deep take 23.9 /
# 22.6 / 22.2 / 22.5 ms at 128 / 256 / 512 / 1,024 keys.  A chunk
# expands a span's keys and values for all heads and scores all its
# queries against them in float32, [heads, queries, keys]: a 512-token
# chunk after 3,072 tokens takes 46.3 / 39.7 / 45.9 / 56.8 ms at 64 /
# 128 / 256 / 512 keys.
_TICK_SPAN_KEYS = 512
_CHUNK_SPAN_KEYS = 128
# The grouped matmul's tiles.  Rows of routed pairs a visit works on (a
# tick of 64 rows reads the same at 32, 64 and 128), and the most bytes
# of one expert's weights a grid step holds: the tile is the WHOLE
# contraction wherever 128 columns of it fit those bytes, then as many
# columns as do (`_tiles`), so an expert's block is fetched once however
# many row tiles its group lies across, in as few grid steps as the
# kernel's fast memory takes double-buffered beside its rows and its
# float32 accumulator (tests/test_tpu_compile.py holds every
# configuration's widest tile to that).
_GMM_ROWS = 128
_GMM_TILE_BYTES = 4 << 20

COUNTERS = ("pairs_routed", "pairs_local", "experts_touched",
            "experts_held", "load_max", "pairs_worked", "expert_reads")
_WORD = 30      # a counter is [hi, lo] with lo < 2**30


@dataclasses.dataclass(frozen=True)
class DeepseekV2Config:
    """Published DeepSeek-V2 sizes by default; `experts_held`,
    `expert_offset` and `vocab_size` say the share this chip holds.
    Hashable: the engine passes it as a static argument."""
    max_seq: int
    n_layers: int = 60
    vocab_size: int = 102400
    d_model: int = 5120
    n_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    d_ff: int = 12288                 # the leading dense layers
    first_k_dense: int = 1
    moe_d_ff: int = 1536
    n_routed_experts: int = 160       # what the router scores: never cut
    n_shared_experts: int = 2
    n_group: int = 8
    topk_group: int = 3
    top_k: int = 6
    routed_scaling_factor: float = 16.0
    experts_held: Optional[int] = None    # None: all of them
    expert_offset: int = 0
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    rope_orig_max: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.experts_held is None:
            object.__setattr__(self, "experts_held", self.n_routed_experts)
        if self.n_routed_experts % self.n_group:
            raise ValueError("n_routed_experts must be whole groups")
        if not 0 < self.topk_group <= self.n_group:
            raise ValueError("topk_group must be 1..n_group")
        if self.top_k > self.topk_group * (self.n_routed_experts
                                           // self.n_group):
            raise ValueError("top_k exceeds the experts of the kept groups")
        if self.expert_offset < 0 or self.experts_held < 1 \
                or self.expert_offset + self.experts_held \
                > self.n_routed_experts:
            raise ValueError("the held experts must lie among the routed")
        if not 0 <= self.first_k_dense <= self.n_layers:
            raise ValueError("first_k_dense must be 0..n_layers")

    @property
    def head_dim(self) -> int:
        """A query / key head's width (what the softmax scale is of)."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def n_moe(self) -> int:
        return self.n_layers - self.first_k_dense

    @property
    def softmax_scale(self) -> float:
        """head_dim^-0.5 times YaRN's attention factor squared."""
        return self.head_dim ** -0.5 * _yarn_mscale(
            self.rope_factor, self.mscale_all_dim) ** 2

    @property
    def paged_body(self) -> PagedBody:
        return BODY


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: DeepseekV2Config) -> np.ndarray:
    """YaRN's rotary frequencies [qk_rope_head_dim / 2]: the published
    ones where a dimension turns more than `beta_fast` times over the
    original context, those divided by `rope_factor` where it turns
    fewer than `beta_slow` times, a linear ramp between."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    exps = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra, inter = base ** -exps, base ** -exps / cfg.rope_factor

    def turns_at(rotations):
        return dim * math.log(cfg.rope_orig_max / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(turns_at(cfg.beta_fast)), 0)
    high = min(math.ceil(turns_at(cfg.beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low)
                   / ((high if high != low else high + 0.001) - low), 0, 1)
    return (inter * ramp + extra * (1 - ramp)).astype(np.float32)


def _rope(x, positions, cfg: DeepseekV2Config):
    """x [n, ..., d] at positions [n]: pairs (i, i + d/2), in float32."""
    half = x.shape[-1] // 2
    ang = positions.astype(jnp.float32)[:, None] * yarn_inv_freq(cfg)[None]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    m = _yarn_mscale(cfg.rope_factor, cfg.mscale) \
        / _yarn_mscale(cfg.rope_factor, cfg.mscale_all_dim)
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos],
                           axis=-1).astype(x.dtype)


def _span_pages(keys: int, page_size: int, nblk: int) -> int:
    return max(1, min(nblk, keys // page_size))


def attn_keys(cfg: DeepseekV2Config, pos: np.ndarray) -> Tuple[int, int]:
    """(keys read, keys held) by one tick's decode rows at positions
    `pos`, summed over rows and layers: every layer attends to all a
    row holds."""
    held = (int(np.asarray(pos).sum()) + len(pos)) * cfg.n_layers
    return held, held


def attn_keys_gathered(cfg, pos: np.ndarray, page_size: int, nblk: int, *,
                       layers: Optional[int] = None) -> int:
    """Latents one tick pulls from the pool, for EVERY row of the call
    (`pos` of all decode rows, idle ones at 0): on a TPU each row's own
    blocks of pages, what the kernel copies; elsewhere whole spans up to
    the deepest row's token, the trip count the span loop reads from
    `pos`.  In each of `layers` layers: all of them by default, the
    latent ones of a model that mixes in others."""
    layers = cfg.n_layers if layers is None else layers
    if _on_tpu():
        token = _lat_width(cfg) * jnp.dtype(cfg.dtype).itemsize
        return _pa.keys_copied(pos, page_size, nblk, token) * layers
    cols = _span_pages(_TICK_SPAN_KEYS, page_size, nblk) * page_size
    spans = -(-(int(np.asarray(pos).max()) + 1) // cols)
    return len(pos) * spans * cols * layers


def check_paging(cfg: DeepseekV2Config, *, page_size: int,
                 prefill_chunk: int, speculate_k: int) -> None:
    if prefill_chunk % page_size:
        raise ValueError(f"a prefill chunk writes whole latent pages: "
                         f"prefill_chunk must be a multiple of page_size="
                         f"{page_size}, got {prefill_chunk}")
    if speculate_k:
        raise NotImplementedError(
            "speculative verify (several tokens a row at per-row "
            "positions) is not written for the absorbed latent step")


# ---------------------------------------------------------------------------
# Weights and cache


def init_params(cfg: DeepseekV2Config, key, dtype=None) -> Dict:
    """Seeded weights, one dict a layer (normal, std 0.02; projections
    back into the residual stream 0.02 / sqrt(2 n_layers); the router in
    float32, as it is applied)."""
    dtype = dtype or cfg.dtype
    D, H, F = cfg.d_model, cfg.n_heads, cfg.moe_d_ff
    qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    s = 0.02
    so = s / np.sqrt(2 * cfg.n_layers)
    keys = iter(jax.random.split(key, 2 + 16 * cfg.n_layers))

    def nrm(shape, scale, dt=dtype):
        return (scale * jax.random.normal(next(keys), shape, jnp.float32)
                ).astype(dt)

    ones = lambda *shape: jnp.ones(shape, jnp.float32)  # noqa: E731

    def swiglu(width, *lead):
        return {"w_gate": nrm(lead + (D, width), s),
                "w_up": nrm(lead + (D, width), s),
                "w_down": nrm(lead + (width, D), so)}

    def layer(i):
        lp = {"ln1": ones(D), "wq_a": nrm((D, qr), s), "q_norm": ones(qr),
              "wq_b": nrm((qr, H * (dn + dr)), s),
              "wkv_a": nrm((D, kr + dr), s), "kv_norm": ones(kr),
              "wk_b": nrm((H, dn, kr), s), "wv_b": nrm((H, kr, dv), s),
              "wo": nrm((H, dv, D), so), "ln2": ones(D)}
        if i < cfg.first_k_dense:
            return dict(lp, **swiglu(cfg.d_ff))
        return dict(lp, router=nrm((D, cfg.n_routed_experts), s,
                                   jnp.float32),
                    shared=swiglu(cfg.n_shared_experts * F),
                    experts=swiglu(F, cfg.experts_held))

    return {"wte": nrm((cfg.vocab_size, D), s),
            "layers": tuple(layer(i) for i in range(cfg.n_layers)),
            "ln_f": ones(D), "wlm": nrm((D, cfg.vocab_size), s)}


def _lat_width(cfg) -> int:
    """A cached row: latent + rotary key part, up to whole tiles."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // 128) * 128


def _lat_row(a, b, cfg):
    """[..., 512] and [..., 64] side by side in a row of the cache's
    width (zeros after them): a token's latent and rotary key part, or a
    query's two parts against them."""
    pad = _lat_width(cfg) - a.shape[-1] - b.shape[-1]
    return jnp.concatenate(
        [a, b, jnp.zeros(a.shape[:-1] + (pad,), a.dtype)], axis=-1)


def init_paged_cache(cfg: DeepseekV2Config, num_pages: int, page_size: int,
                     num_slots: Optional[int] = None) -> Dict:
    return {"lat": jnp.zeros((cfg.n_layers, num_pages, page_size,
                              _lat_width(cfg)), cfg.dtype),
            "moe": jnp.zeros((len(COUNTERS), 2), jnp.int32)}


def read_counters(cache: Dict, cfg: DeepseekV2Config) -> Dict[str, Any]:
    """The expert layers' cumulative counters as the engine's stats
    carry them (`moe_<name>`; a host copy of 56 bytes; only the thread
    that owns the cache may call it: every step donates the cache; or
    of a `snapshot_counters` of it, at any time).
    `pairs_routed`: tokens x top_k x expert layers, over ticks' live rows
    and chunks' real tokens; `pairs_local`: those whose expert is held
    here; `experts_touched` / `experts_held`: held experts with a token /
    held experts, per tick and expert layer; `load_max`: the busiest
    held expert's tokens, per call and expert layer, and `load_mean`
    the mean over the held beside it (= pairs_local / experts held);
    `pairs_worked`: the rows `routed_experts`' slabs covered (trips x
    slab), summed as `pairs_local` is: their ratio is what the layer
    worked on for each pair it had to; `expert_reads`: the times one
    projection's walk fetched an expert's weights from the device's
    memory (`_reads`: once a slab the expert has rows in, where a grid
    step holds the whole contraction), per tick and expert layer as
    `experts_touched` is: their ratio is 1 where every touched expert
    crossed the memory once."""
    words = np.asarray(cache["moe"]).astype(np.int64)
    counts = {name: int((hi << _WORD) + lo)
              for name, (hi, lo) in zip(COUNTERS, words)}
    counts["load_mean"] = counts["pairs_local"] / cfg.experts_held
    return counts


def snapshot_counters(cache: Dict) -> Dict:
    """A device copy of the counters as the step that produced `cache`
    leaves them, dispatched behind that step: what `read_counters` takes
    once the cache itself has been donated to the next step, so a loop
    that reads a tick one turn late reads them with its tokens and
    waits for no newer step."""
    snap = jnp.copy(cache["moe"])
    snap.copy_to_host_async()
    return {"moe": snap}


def _count(counters, adds):
    lo = counters[:, 1] + jnp.stack(adds).astype(jnp.int32)
    return jnp.stack([counters[:, 0] + (lo >> _WORD),
                      lo & ((1 << _WORD) - 1)], axis=1)


# ---------------------------------------------------------------------------
# The expert layer


def route(router, h, cfg: DeepseekV2Config):
    """Group-limited greedy top-k over ALL routed experts, in float32.
    h [N, D] -> (expert ids [N, top_k], weights [N, top_k] float32):
    softmax scores; a group scores its best expert; the `topk_group`
    best groups are kept; the top_k best experts inside them; weights
    are the scores themselves (not renormalised) times
    `routed_scaling_factor`."""
    N = h.shape[0]
    logits = jnp.einsum("nd,de->ne", h.astype(jnp.float32),
                        router.astype(jnp.float32), precision=_HI)
    p = jax.nn.softmax(logits, axis=-1)
    per = cfg.n_routed_experts // cfg.n_group
    best = p.reshape(N, cfg.n_group, per).max(-1)
    kept = lax.top_k(best, cfg.topk_group)[1]                # [N, groups]
    in_kept = (kept[:, :, None] == jnp.arange(cfg.n_group)[None, None]
               ).any(1)                                      # [N, n_group]
    w, ids = lax.top_k(jnp.where(jnp.repeat(in_kept, per, axis=1), p, 0.0),
                       cfg.top_k)
    return ids.astype(jnp.int32), w * cfg.routed_scaling_factor


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _tile(width: int, most: int) -> int:
    """The largest multiple of 128 up to `most` that divides `width`,
    else the whole of it (toy widths)."""
    for t in range(most - most % 128, 0, -128):
        if width % t == 0:
            return t
    return width


def _tiles(K: int, N: int, itemsize: int) -> Tuple[int, int]:
    """(contraction, columns) of the weight block one grid step of the
    grouped matmul holds for experts of [K, N]: the largest under
    `_GMM_TILE_BYTES`, the contraction before the columns."""
    tk = _tile(K, max(128, _GMM_TILE_BYTES // (128 * itemsize)))
    return tk, _tile(N, max(128, _GMM_TILE_BYTES // (tk * itemsize)))


def _grouped(rows, w, sizes, out_dtype, tm):
    """rows [M, K] sorted by group, w [G, K, N], sizes [G] -> [M, N]:
    row r of group g times w[g].  Rows past the last group are not
    visited and come back undefined."""
    return gmm(rows, w, sizes, preferred_element_type=out_dtype,
               tiling=(tm,) + _tiles(w.shape[1], w.shape[2],
                                     w.dtype.itemsize),
               interpret=not _on_tpu())


def _slab(N: int, k: int, cfg) -> Tuple[int, int, int]:
    """(the grouped matmul's row tile, the rows of one slab of
    `routed_experts`, the slabs N x k pairs can fill) for N tokens of k
    choices each, in whole tiles.  Where every routed expert is held
    here every pair is, and the slab is the call: one sort, one gather,
    one grouped matmul a projection, each expert read once.  Where a
    share is held a slab is as many pairs as the call has tokens, so the
    pairs of a call take at most k trips and as few as the chip's share
    of the experts asks."""
    tm = min(_GMM_ROWS, -(-N * k // 8) * 8)
    rows = N * k if cfg.experts_held == cfg.n_routed_experts else N
    S = -(-rows // tm) * tm
    return tm, S, -(-N * k // S)


def _trips(held, S: int, most: int):
    """The slabs `held` pairs fill; where all the call's pairs fit one
    slab there is nothing to walk and it runs once, whatever is held (a
    loop whose trips the device counts, run once, read 0.3 ms more in a
    tick of 20 top-1 expert layers than the same slab in line)."""
    return 1 if most == 1 else -(-held // S)


def routed_experts(experts, h, ids, weights, live, cfg: DeepseekV2Config):
    """The held experts' part of the layer: for each token of h [N, D]
    the sum over its chosen experts THAT ARE HELD HERE of weight x
    SwiGLU_expert(h); a token none of whose experts is held gets zeros,
    and so does a token that is not `live` [N] (an idle decode row, a
    chunk's pad), which also counts nowhere.  Nothing is dropped: the
    (token, expert) pairs are sorted by expert, the held ones first,
    and every held one runs.  The work follows the pairs held, not the
    pairs routed: they are walked in slabs of `_slab` rows, as many
    trips as they fill (`_trips`: none where nothing is held, one where
    the call's pairs fit a slab, which they do wherever every expert is
    held), and a trip gathers its rows, runs one grouped matmul a
    projection over its share of each expert's group (an expert no
    token chose is not read, one whose group lies across row tiles is
    read once: `_tiles`) and adds its weighted rows to their tokens.
    Only a call that holds every expert has an array with a row a
    routed pair.
    Returns ([N, D] float32, tokens on each held expert [experts_held])."""
    N, D = h.shape
    k, E = cfg.top_k, cfg.experts_held
    dt = h.dtype
    P = N * k
    tm, S, most = _slab(N, k, cfg)
    Pp = most * S
    local = ids - cfg.expert_offset
    held = (local >= 0) & (local < E) & live[:, None]        # [N, k]
    # One sort of (group, pair) keys: E = not here, sorts last; a pad
    # after them.  The held pairs are then order[:n], n = sizes.sum().
    bits = (P - 1).bit_length()
    keys = jnp.sort((jnp.where(held, local, E).reshape(P) << bits)
                    | jnp.arange(P))
    ends = jnp.searchsorted(keys, jnp.arange(1, E + 1) << bits
                            ).astype(jnp.int32)              # [E]
    sizes = jnp.diff(ends, prepend=0)
    order = jnp.pad(keys & ((1 << bits) - 1), (0, Pp - P))
    w_gate, w_up, w_down = (experts[n].astype(dt)
                            for n in ("w_gate", "w_up", "w_down"))

    def slab(lo):
        """The S pairs from `lo` on, and their experts' outputs [S, D]
        float32 (rows past the held pairs come back undefined)."""
        pair = lax.dynamic_slice(order, (lo,), (S,))
        # the groups, cut to the slab
        part = jnp.diff(jnp.clip(ends - lo, 0, S), prepend=0)
        rows = h[pair // k]                                  # [S, D]
        mid = jax.nn.silu(_grouped(rows, w_gate, part, dt, tm)) \
            * _grouped(rows, w_up, part, dt, tm)
        return pair, _grouped(mid, w_down, part, jnp.float32, tm)

    def trip(s, acc):
        pair, out = slab(s * S)
        out = jnp.where((s * S + jnp.arange(S) < ends[-1])[:, None],
                        out * weights.reshape(P)[pair][:, None], 0.0)
        to = ((pair // k)[None, :] == jnp.arange(N)[:, None]
              ).astype(jnp.float32)
        return acc + jnp.einsum("ns,sd->nd", to, out, precision=_HI)

    none = jnp.zeros((N, D), jnp.float32)
    if most > 1:
        return lax.fori_loop(0, _trips(ends[-1], S, most), trip, none), sizes
    if k == 1:          # a token has one row: nothing to sum
        return trip(0, none), sizes
    # One slab of every pair: a token's k rows lie where the sort put
    # them, so the sum back is each pair's rank (the sort's inverse) and
    # a sum over k, not a [N, P] one-hot product (2.05 against 2.14 ms a
    # layer at 512 tokens of top-8; PERF.md section 6, PR 62).
    _, out = slab(0)
    rank = jnp.argsort(order[:P])
    return jnp.where(held[:, :, None], out[rank].reshape(N, k, D)
                     * weights[:, :, None], 0.0).sum(1), sizes


def count_routed(counts, live, sizes, is_tick: bool, cfg):
    """`counts` (a call's additions to COUNTERS so far) plus one expert
    layer's: `live` [N] the tokens routed, `sizes` [experts_held] the
    tokens on each held expert (routed_experts' second result), from
    which the rows its slabs covered follow as its trips do, and the
    times a projection's walk fetched an expert's weights (`_reads`)."""
    tick = jnp.int32(is_tick)
    tm, S, most = _slab(live.shape[0], cfg.top_k, cfg)
    touched = (sizes > 0).sum()
    return [c + a for c, a in zip(counts, (
        live.sum() * cfg.top_k, sizes.sum(), tick * touched,
        tick * cfg.experts_held, sizes.max(),
        _trips(sizes.sum(), S, most) * S,
        _reads(sizes, touched, tm, S, most, cfg) if is_tick else 0))]


def _reads(sizes, touched, tm: int, S: int, most: int, cfg):
    """How often the walk of one projection (gate's: [d_model, moe_d_ff]
    an expert) fetches an expert's weights, as the grouped matmul's
    schedule goes: within a slab it visits (expert, row tile) pairs in
    order of expert.  Where a grid step holds the whole contraction a
    visit that follows one of the same expert fetches nothing, so an
    expert is read once a slab it has rows in; where it does not, every
    visit reads the expert again."""
    tk, _ = _tiles(cfg.d_model, cfg.moe_d_ff, jnp.dtype(cfg.dtype).itemsize)
    unit = S if tk == cfg.d_model else tm
    if most == 1 and unit == S:
        return touched
    ends = jnp.cumsum(sizes)
    return jnp.where(sizes > 0,
                     (ends - 1) // unit - (ends - sizes) // unit + 1, 0).sum()


def _ffn(lp, x, live, is_tick, counts, cfg: DeepseekV2Config):
    """x + FFN(norm(x)): dense SwiGLU in the leading layers, shared +
    held routed experts after them.  `counts`: this call's additions to
    COUNTERS so far."""
    dt = cfg.dtype
    h = _rmsnorm(x, lp["ln2"])
    if "router" not in lp:
        return x + _swiglu(lp, h, dt), counts
    with jax.named_scope("moe_route"):
        ids, weights = route(lp["router"], h, cfg)
    with jax.named_scope("moe_experts"):
        routed, sizes = routed_experts(lp["experts"], h, ids, weights, live,
                                       cfg)
    counts = count_routed(counts, live, sizes, is_tick, cfg)
    return x + (routed + _swiglu(lp["shared"], h, dt)).astype(x.dtype), counts


# ---------------------------------------------------------------------------
# Latent attention, for a single-row chunk of T tokens (x [T, D]) and
# for a tick of B rows (x [B, D]).  A second model runs the same two
# paths over the same cached row (models/bailing_hybrid.py: one full-rank
# query projection, interleaved RoPE, a gate a head before `wo`): what
# differs reaches `_attn_chunk` and `_attn_tick` as `project` (this
# file's `_project` by default) and `gate` (none by default), and its
# config answers `n_heads`, `kv_lora_rank`, `qk_rope_head_dim`,
# `v_head_dim`, `softmax_scale` and `dtype` as this one does.  A third
# (models/glm_moe_dsa.py: 64 heads of 192 + 64 | 256 over the same row)
# CHOOSES the keys a query attends to and says which as `chosen`: a
# chunk takes a 0/1 mask over its row's table width and attends
# expanded under it, a tick a `Chosen` (the same mask a row, and how to
# list it) and either walks each row's own pages under the mask through
# `ops/paged_attention.py` or gathers the listed latents, and only
# those, out of the pool (`_attend_chosen`), whichever `walks` says is
# cheaper at the rows' depths; all under the scope `dsa_attend`.


def _project(lp, x, positions, cfg: DeepseekV2Config):
    """x [n, D] at positions [n] -> q_nope [n, H, 128], rotated q_pe
    [n, H, 64], the normed latent [n, 512], the rotated shared key part
    [n, 64]."""
    dt = cfg.dtype
    n, H = x.shape[0], cfg.n_heads
    h = _rmsnorm(x, lp["ln1"])
    qa = _rmsnorm(jnp.einsum("nd,dr->nr", h, lp["wq_a"].astype(dt)),
                  lp["q_norm"])
    q = jnp.einsum("nr,rf->nf", qa, lp["wq_b"].astype(dt)
                   ).reshape(n, H, cfg.head_dim)
    kva = jnp.einsum("nd,dr->nr", h, lp["wkv_a"].astype(dt))
    ckv = _rmsnorm(kva[:, :cfg.kv_lora_rank], lp["kv_norm"])
    kpe = _rope(kva[:, cfg.kv_lora_rank:], positions, cfg)
    q_nope = q[..., :cfg.qk_nope_head_dim]
    q_pe = _rope(q[..., cfg.qk_nope_head_dim:], positions, cfg)
    return q_nope, q_pe, ckv, kpe


def _merge(part, scores, values_of):
    """One span into a running softmax: `part` = (maxima, sums,
    accumulator [..., dv]); `scores` float32, masked with -inf;
    `values_of(weights)` the span's weighted values in float32."""
    top, total, acc = part
    new = jnp.maximum(top, scores.max(-1))
    ref = jnp.where(jnp.isfinite(new), new, 0.0)
    e = jnp.exp(scores - ref[..., None])
    keep = jnp.exp(top - ref)
    return (new, total * keep + e.sum(-1),
            acc * keep[..., None] + values_of(e))


class Chosen(NamedTuple):
    """The keys each row of a tick attends to, where a model chooses
    them a token (models/glm_moe_dsa.py's indexer): `keep` [B, S] bool
    over each row's OWN sequence positions, S the table's width in keys
    (what a walk of the row's pages is masked by); `listed`, which turns
    it into lists when called, on the branch that gathers and nowhere
    else: (`idx` [B, k] the kept positions, `ok` [B, k] which of the k
    slots hold one: a row that holds fewer than k keys fills fewer);
    `walk`, a boolean the device reads: whether this tick walks
    (`walks`, the same in every layer); and the rows attended at all:
    order[0] .. order[n - 1] of `order`, a permutation of the rows, `n`
    a number the device reads (a tick's live rows: those past position
    0).  The others get zeros."""
    keep: Any
    listed: Callable[[], Tuple[Any, Any]]
    walk: Any
    n: Any
    order: Any


# Rows whose chosen latents one trip of a tick's gather holds side by
# side: 8 x 2,048 rows of 640 are 21 MB, their float32 scores over 64
# heads 4 MB.  (A trip a row read the same; a trip is ~14 device
# operations, and a profiler's capture pays by the operation.)
_CHOSEN_ROWS = 8


# What the two fetches of a selecting tick cost on a v5e, in ns: a key a
# row HOLDS where `ops/paged_attention.py` walks the row's every page
# under the mask, and a key a row CHOSE where `_attend_chosen` lists and
# gathers it.  Measured at GLM-5's widths (64 heads over 1,280 B
# latents, blocks of 768, 2,048 chosen a row; PERF.md section 6, PR 67):
# the walk 2.08 / 1.98 / 2.01 / 2.02 / 1.98 a key held at 8k / 12k / 16k
# / 20k / 34k keys a row (1 to 3 % of it the mask), the gather 18.6 a
# key chosen while the rows' deepest lies in a quarter of the table,
# 21.9 to 27.1 past 20k (the listing runs over the whole width and a
# trip of eight rows is seldom full): 28 rows at 20k walk in 40 us a row
# and gather in 50, 24 at 24k in 48 and 45, 16 at 34k in 67 and 49.
_WALK_NS_A_KEY = 2.0
_GATHER_NS_A_KEY = 22.0


def walks(pos, k: int):
    """Whether a tick of rows at `pos` (numpy or traced; idle ones at 0)
    that each chose up to `k` keys fetches them by a walk of each row's
    pages: where that costs less than the gather of k rows a live row,
    whatever it holds.  Read from the input, once a tick."""
    return pos.sum().astype("float32") * _WALK_NS_A_KEY \
        < (pos > 0).sum().astype("float32") * (k * _GATHER_NS_A_KEY)


def _live_block(order, n, j, qb: int):
    """Trip j of a walk over the first `n` rows of `order`, `qb` at a
    time: (the rows it takes [qb], which of them are among the n)."""
    return (lax.dynamic_slice_in_dim(order, j * qb, qb),
            j * qb + jnp.arange(qb) < n)


def _attend_chosen(q_row, lat, l, bt, chosen: Chosen, cfg):
    """Absorbed attention over the chosen keys and nothing else: q_row
    [B, H, W] (laid as a cached row is, `wk_b` already in it) against
    the latents at positions `chosen.idx` of each row's sequence,
    gathered `_CHOSEN_ROWS` rows a trip out of layer `l` of the pool,
    each through its own table `bt[row]` -> (the weighted latents [B,
    H, kv_lora_rank], the latent rows the trips gathered AND weighed
    for the `chosen.n` rows: their `ok` slots, counted as the trips
    went).  The trip count follows `chosen.n`: a block of idle rows
    costs nothing.  (A position's page is found by comparison with the
    table's columns, a masked sum over `nblk`: the same lookup as a
    gather of single numbers took 5 ns each on a v5e, a quarter of a
    trip; PERF.md section 6, PR 65.)"""
    B, H, W = q_row.shape
    psz, nblk, kr = lat.shape[2], bt.shape[1], cfg.kv_lora_rank
    dt = cfg.dtype
    qb = math.gcd(B, _CHOSEN_ROWS)
    listed, filled = chosen.listed()

    def trip(j, carry):
        out, took = carry
        at, on = _live_block(chosen.order, chosen.n, j, qb)
        idx, ok = listed[at], filled[at] & on[:, None]
        page = jnp.where((idx // psz)[..., None] == jnp.arange(nblk),
                         bt[at][:, None, :], 0).sum(-1)
        rows = lat[l, page, idx % psz]                       # [qb, k, W]
        s = jnp.einsum("qhc,qsc->qhs", q_row[at], rows,
                       preferred_element_type=jnp.float32) \
            * cfg.softmax_scale
        p = jax.nn.softmax(jnp.where(ok[:, None, :], s, -jnp.inf), axis=-1)
        o = jnp.einsum("qhs,qsc->qhc", p.astype(dt), rows[..., :kr])
        # (a row past `n` in the last block has no slot filled)
        return (out.at[at].set(jnp.where(on[:, None, None], o, 0)),
                took + ok.sum(dtype=jnp.int32))

    return lax.fori_loop(0, -(-chosen.n // qb), trip,
                         (jnp.zeros((B, H, kr), dt), jnp.int32(0)))


def _attn_chunk(lp, x, l, cache, bt, start, cfg, project=None, gate=None,
                chosen=None):
    """`project` (lp, x, positions, cfg) -> (q_nope, q_pe, the normed
    latent, the rotated shared key part), `_project` by default; `gate`
    (lp, x) -> a factor a token and head [T, H] on the heads' outputs
    before `wo`, none by default; `chosen` [T, S] bool over the table's
    width S, where the model chooses the keys a query attends to: a
    query's softmax then runs over its chosen keys alone."""
    T = x.shape[0]
    H, psz, kr = cfg.n_heads, cache["lat"].shape[2], cfg.kv_lora_rank
    dt = cfg.dtype
    cols = start + jnp.arange(T)
    q_nope, q_pe, ckv, kpe = (project or _project)(lp, x, cols, cfg)
    pages = lax.dynamic_slice(bt, (start // psz,), (T // psz,))
    lat = cache["lat"].at[l, pages].set(
        _lat_row(ckv, kpe, cfg).reshape(T // psz, psz, -1))

    with jax.named_scope("mla_expand_attend" if chosen is None
                         else "dsa_attend"):
        nblk = bt.shape[0]
        span = _span_pages(_CHUNK_SPAN_KEYS, psz, nblk)
        width = span * psz
        wk_b, wv_b = lp["wk_b"].astype(dt), lp["wv_b"].astype(dt)

        def attend(i, part):
            first = jnp.minimum(i * span, nblk - span)   # as the slice clamps
            pg = lax.dynamic_slice(bt, (first,), (span,))
            rows = lat[l, pg].reshape(width, -1)
            c, r = rows[:, :kr], rows[:, kr:kr + cfg.qk_rope_head_dim]
            k_nope = jnp.einsum("sc,hnc->shn", c, wk_b)
            v = jnp.einsum("sc,hcv->shv", c, wv_b)
            s = (jnp.einsum("thn,shn->hts", q_nope, k_nope,
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("thr,sr->hts", q_pe, r,
                              preferred_element_type=jnp.float32)
                 ) * cfg.softmax_scale
            kcols = first * psz + jnp.arange(width)
            seen = (kcols[None, :] <= cols[:, None]) \
                & (kcols[None, :] >= i * width)
            if chosen is not None:
                seen &= lax.dynamic_slice(chosen, (0, first * psz),
                                          (T, width))
            s = jnp.where(seen[None], s, -jnp.inf)
            return _merge(part, s, lambda e: jnp.einsum(
                "hts,shv->htv", e.astype(dt), v,
                preferred_element_type=jnp.float32))

        stat = jnp.full((H, T), -jnp.inf, jnp.float32)
        _, total, acc = lax.fori_loop(
            0, (start + T + width - 1) // width, attend,
            (stat, jnp.zeros_like(stat),
             jnp.zeros((H, T, cfg.v_head_dim), jnp.float32)))
        out = (acc / total[..., None]).astype(dt)             # [H, T, dv]
    if gate is not None:
        out = out * gate(lp, x).T[:, :, None].astype(dt)
    x = x + jnp.einsum("htv,hvd->td", out, lp["wo"].astype(dt))
    return x, dict(cache, lat=lat)


def _attend_under(q_row, lat, l, bt, pos, chosen: Chosen, cfg):
    """The absorbed attention of a tick that chose its keys, by the
    cheaper fetch (`chosen.walk`): each row's own pages walked under
    `chosen.keep` by the ragged kernel, where there is a TPU to run it,
    or the listed latents gathered (`_attend_chosen`) -> (the weighted
    latents [B, H, kv_lora_rank], the keys weighed, counted where they
    were, the rows that walked)."""
    def walk():
        o, weighed = _pa.paged_attention(
            q_row, lat, None, l, bt, pos, n_kv_heads=1,
            value_width=cfg.kv_lora_rank, scale=cfg.softmax_scale,
            keep=chosen.keep)
        return o, weighed.sum(), chosen.n

    def gather():
        return _attend_chosen(q_row, lat, l, bt, chosen, cfg) \
            + (jnp.zeros_like(chosen.n),)
    return lax.cond(chosen.walk, walk, gather) if _on_tpu() else gather()


def _attn_tick(lp, x, l, cache, bt, pos, cfg, project=None, gate=None,
               chosen=None):
    """`project` and `gate` as `_attn_chunk` takes them; `chosen` a
    `Chosen`, with which (the keys the attention weighed, the rows that
    walked their pages for them) are returned third."""
    psz, kr = cache["lat"].shape[2], cfg.kv_lora_rank
    dt = cfg.dtype
    q_nope, q_pe, ckv, kpe = (project or _project)(lp, x, pos, cfg)
    page = jnp.take_along_axis(bt, (pos // psz)[:, None], axis=1)[:, 0]
    lat = cache["lat"].at[l, page, pos % psz].set(_lat_row(ckv, kpe, cfg))

    with jax.named_scope("mla_absorb_attend" if chosen is None
                         else "dsa_attend"):
        # wk_b into the query, wv_b into the output: scores and values
        # are taken against the cached rows themselves
        q_row = _lat_row(jnp.einsum("bhn,hnc->bhc", q_nope,
                                    lp["wk_b"].astype(dt)), q_pe, cfg)
        if chosen is not None:
            o_lat, *counted = _attend_under(q_row, lat, l, bt, pos, chosen,
                                            cfg)
        elif _on_tpu():
            o_lat = _pa.paged_attention(
                q_row, lat, None, l, bt, pos, n_kv_heads=1, value_width=kr,
                scale=cfg.softmax_scale)
        else:
            o_lat = _span_tick(q_row, lat, l, bt, pos, cfg)
        out = jnp.einsum("bhc,hcv->bhv", o_lat, lp["wv_b"].astype(dt))
    if gate is not None:
        out = out * gate(lp, x)[:, :, None].astype(dt)
    x = x + jnp.einsum("bhv,hvd->bd", out, lp["wo"].astype(dt))
    cache = dict(cache, lat=lat)
    return (x, cache) if chosen is None else (x, cache, counted)


def _span_tick(q_row, lat, l, bt, pos, cfg):
    """The tick's attention over the latent pages of layer `l` where
    there is no TPU, and what the kernel is held equal to: a loop whose
    trip count is the DEEPEST row's depth gathers, for every row of the
    call, a span of `_TICK_SPAN_KEYS` latents a trip and masks what a
    shallower row does not hold.  q_row [B, H, 640] (laid as a cached
    row is) -> the weighted latents [B, H, 512]."""
    B, H, _ = q_row.shape
    psz, nblk, kr = lat.shape[2], bt.shape[1], cfg.kv_lora_rank
    dt = cfg.dtype
    span = _span_pages(_TICK_SPAN_KEYS, psz, nblk)
    width = span * psz

    def attend(i, part):
        first = jnp.minimum(i * span, nblk - span)
        pg = lax.dynamic_slice(bt, (0, first), (B, span))
        rows = lat[l, pg].reshape(B, width, -1)
        s = jnp.einsum("bhc,bsc->bhs", q_row, rows,
                       preferred_element_type=jnp.float32) \
            * cfg.softmax_scale
        kcols = first * psz + jnp.arange(width)
        seen = (kcols[None, :] <= pos[:, None]) \
            & (kcols[None, :] >= i * width)
        s = jnp.where(seen[:, None], s, -jnp.inf)
        return _merge(part, s, lambda e: jnp.einsum(
            "bhs,bsc->bhc", e.astype(dt), rows[..., :kr],
            preferred_element_type=jnp.float32))

    stat = jnp.full((B, H), -jnp.inf, jnp.float32)
    _, total, acc = lax.fori_loop(
        0, (jnp.max(pos) + width) // width, attend,
        (stat, jnp.zeros_like(stat), jnp.zeros((B, H, kr), jnp.float32)))
    return (acc / total[..., None]).astype(dt)


# ---------------------------------------------------------------------------
# The paged step


def _through_layers(params, x, cache, live, is_tick, attn, cfg):
    counts = [jnp.int32(0)] * len(COUNTERS)
    for l, lp in enumerate(params["layers"]):
        x, cache = attn(lp, x, l, cache)
        x, counts = _ffn(lp, x, live, is_tick, counts, cfg)
    x = _rmsnorm(x, params["ln_f"])
    logits = jnp.einsum("nd,dv->nv", x.astype(cfg.dtype),
                        params["wlm"].astype(cfg.dtype),
                        preferred_element_type=jnp.float32)
    return logits, dict(cache, moe=_count(cache["moe"], counts))


def paged_chunk_step(params: Dict, tokens, pos, cache: Dict, block_tables,
                     cfg: DeepseekV2Config, pad_lo=None, slot=None,
                     valid=None) -> Tuple[Any, Dict]:
    """The model's paged step, under decode.paged_chunk_step's contract.

    `pos` a scalar: ONE row's chunk of T tokens starting there (T and
    `pos` whole pages) — single-row prefill, the EXPANDED attention.  It
    fills the row's latent pages; only the first `valid` tokens (default
    all) are routed to experts (`slot` is taken and unused: no state
    lives outside the pages).  `pos` a [B] vector with one token a row:
    the decode tick, the ABSORBED attention.  Rows at position 0 are
    idle: their writes land wherever their block table points (the trash
    page) and they are routed nowhere.
    Returns (logits [B, t, V] float32, cache)."""
    if pad_lo is not None:
        raise NotImplementedError("left-padded rows")
    B, t = tokens.shape
    psz = cache["lat"].shape[2]
    pos = jnp.asarray(pos, jnp.int32)
    embed = lambda tok: jnp.take(params["wte"], tok, axis=0  # noqa: E731
                                 ).astype(cfg.dtype)
    if pos.ndim == 0:
        if B != 1 or t % psz:
            raise ValueError(f"a chunk is one row of whole pages of {psz} "
                             f"tokens, got {tokens.shape}")
        live = jnp.arange(t) < (t if valid is None
                                else jnp.asarray(valid, jnp.int32))
        bt = block_tables[0]
        logits, cache = _through_layers(
            params, embed(tokens[0]), cache, live, False,
            lambda lp, x, l, c: _attn_chunk(lp, x, l, c, bt, pos, cfg), cfg)
        return logits[None], cache
    if t != 1:
        raise NotImplementedError(
            "several tokens a row at per-row positions (speculative "
            "verify) are not written for the absorbed latent step")
    logits, cache = _through_layers(
        params, embed(tokens[:, 0]), cache, pos > 0, True,
        lambda lp, x, l, c: _attn_tick(lp, x, l, c, block_tables, pos, cfg),
        cfg)
    return logits[:, None], cache


# A page here is latents, not K then V of [page, Hkv, Dh] (not `framed`):
# what frames pages refuses this model by name (kv_tier.refuse_unframed).
BODY = PagedBody(
    init_paged_cache=init_paged_cache, paged_chunk_step=paged_chunk_step,
    check_paging=check_paging, attn_keys=attn_keys, page_keys=("lat",),
    attn_keys_gathered=attn_keys_gathered,
    snapshot_counters=snapshot_counters, read_counters=read_counters)
