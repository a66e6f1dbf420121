"""MiniCPM-SALA: a decoder that mixes two kinds of layer in a fixed,
irregular order — block-sparse GQA attention (InfLLM-v2, the `minicpm4`
mixer) and lightning linear attention (the `lightning-attn` mixer) —
under the MiniCPM family's muP scaling.  This module is the model as the
serving engine runs it: a config object, seeded weights, the cache it
declares, and its own paged step for a prefill chunk and for a decode
tick, bound into one declared body (`BODY`, a decode.PagedBody) that the
config names, so the engine's two jitted programs
(`engine._prefill_chunk`, `engine._paged_tick`) run it as they run every
model.

The cache (one pytree, `engine._cache`):

  k, v    [A, P, G, page, Dh]   pages of the A attention layers, a KV
                                group's 64 x 128 keys contiguous (a
                                [.., 2, 128] minor pair would be padded
                                to the chip's tile)
  kc      [A, P, G, 4, Dh] f32  the block scorer's compressed keys: the
                                mean of 32 keys at stride 16, kept with
                                the page in which a kernel STARTS.  A
                                cache of their own: recomputing them
                                from the pages would read every key of
                                the context each tick, which is what
                                selection exists to avoid
  state   [N, B, Hl, Dl, Dl] f32  the N lightning layers' recurrent
                                state, one per decode row (not paged)

A page is one selection block (`page_size` must equal `cfg.block`).
A tick gathers, per row and KV group, at most `dense_len / block` pages:
the row's first ones below `dense_len`, the `topk` chosen ones above it
— never the virtual width.  A chunk below `dense_len` attends to the
row's first pages (the power-of-two bucket of them that holds its last
token).  A chunk past it gathers nothing: the selection is a mask
(`chosen_blocks`: the set `select_blocks` would list, found without a
sort), and one kernel a layer (ops/paged_prefill_attention.py) walks the
row's pages once for each query block, the 64 tokens of one page of the
chunk, and scores them against a page under that mask.

What the engine has to know: a row's state is zeroed by the chunk that
starts at position 0 (inside the program); the chunk writes the state of
`slot` and stops moving it after `valid` tokens; the tick leaves rows at
position 0 (idle rows, and the row a prefill is filling) untouched.
(The same contract holds exaone_moe.py's window rings and jamba.py's
scan state and convolution tail: its third user.)
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.models.decode import PagedBody, _rope_at, _swiglu
from ray_tpu.models.gpt import _rmsnorm
from ray_tpu.ops.paged_prefill_attention import query_block_attention

ATTN, LIN = "minicpm4", "lightning-attn"
_HI = lax.Precision.HIGHEST
_DENSE_SPAN_KEYS = 4096      # keys one softmax part of a dense chunk spans


@dataclasses.dataclass(frozen=True)
class SalaConfig:
    """Published MiniCPM-SALA sizes by default; `mixer_types` is the
    layer order.  Hashable: the engine passes it as a static argument."""
    mixer_types: Tuple[str, ...]
    max_seq: int
    vocab_size: int = 73448
    d_model: int = 4096
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    d_ff: int = 16384
    lin_heads: int = 32
    lin_head_dim: int = 128
    rope_theta: float = 10000.0
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    mup_depth: int = 32          # the PUBLISHED depth, also in a depth cut
    dim_model_base: int = 256
    # InfLLM-v2 selection (MiniCPM4's published sparse_config)
    block: int = 64
    kernel: int = 32
    stride: int = 16
    init_blocks: int = 1
    local_blocks: int = 32
    topk: int = 64
    dense_len: int = 8192
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        bad = set(self.mixer_types) - {ATTN, LIN}
        if bad:
            raise ValueError(f"unknown mixer types {sorted(bad)}")
        if self.kernel != 2 * self.stride or self.block != 4 * self.stride:
            raise ValueError("the scorer is written for kernels of two "
                             "strides and blocks of four")
        if self.dense_len % self.block \
                or self.dense_len // self.block < self.topk \
                or self.topk < self.init_blocks + self.local_blocks:
            raise ValueError("dense_len must be whole blocks, at least "
                             "topk of them, and topk must hold the forced "
                             "blocks")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")

    @property
    def n_layers(self) -> int:
        return len(self.mixer_types)

    @property
    def n_attn(self) -> int:
        return sum(m == ATTN for m in self.mixer_types)

    @property
    def n_lin(self) -> int:
        return self.n_layers - self.n_attn

    @property
    def runs(self) -> Tuple[Tuple[str, int, int], ...]:
        """Runs of equal layers in order: (kind, count, index of the
        run's first layer among the layers of its kind)."""
        out, seen = [], {ATTN: 0, LIN: 0}
        for m in self.mixer_types:
            if out and out[-1][0] == m:
                out[-1][1] += 1
            else:
                out.append([m, 1, seen[m]])
            seen[m] += 1
        return tuple((k, n, i0) for k, n, i0 in out)

    @property
    def res_scale(self) -> float:
        return self.scale_depth / float(np.sqrt(self.mup_depth))

    @property
    def paged_body(self) -> PagedBody:
        return BODY


def attn_keys(cfg: SalaConfig, pos: np.ndarray) -> Tuple[int, int]:
    """(keys read, keys held) by one tick's decode rows at positions
    `pos`, summed over rows and attention layers (numpy, host side; the
    engine's `attn_keys_attended` / `attn_keys_resident`).  Derived from
    the positions, as the tick's mask is: a row past `dense_len` reads
    `topk` blocks, its own one as far as its position."""
    pos = np.asarray(pos)
    sparse = (cfg.topk - 1) * cfg.block + pos % cfg.block + 1
    read = np.where(pos >= cfg.dense_len, sparse, pos + 1)
    return (int(read.sum()) * cfg.n_attn,
            (int(pos.sum()) + len(pos)) * cfg.n_attn)


def chunk_selects(cfg: SalaConfig, start: int) -> bool:
    """Whether the prefill chunk that starts at `start` selects pages
    (`check_paging` holds a chunk to one side of `dense_len`)."""
    return start >= cfg.dense_len


def check_paging(cfg: SalaConfig, *, page_size: int, prefill_chunk: int,
                 speculate_k: int) -> None:
    if page_size != cfg.block:
        raise ValueError(f"a page is a selection block: page_size must "
                         f"be {cfg.block}, got {page_size}")
    if prefill_chunk % cfg.block or cfg.dense_len % prefill_chunk:
        raise ValueError(f"prefill_chunk must be whole blocks of "
                         f"{cfg.block} and divide dense_len="
                         f"{cfg.dense_len}, got {prefill_chunk}")
    if speculate_k:
        raise NotImplementedError(
            "speculative verify on a model with per-row recurrent state "
            "needs the state rolled back to the accepted token")


# ---------------------------------------------------------------------------
# Weights


def init_params(cfg: SalaConfig, key, dtype=None) -> Dict:
    """Seeded weights: one stack per run of equal layers, in order."""
    dtype = dtype or cfg.dtype
    D, H, G, Dh, F = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                      cfg.head_dim, cfg.d_ff)
    Hl, Dl = cfg.lin_heads, cfg.lin_head_dim
    s = 0.02
    so = s / np.sqrt(2 * cfg.n_layers)
    keys = iter(jax.random.split(key, 2 + 8 * len(cfg.runs)))

    def nrm(shape, scale):
        return (scale * jax.random.normal(next(keys), shape, jnp.float32)
                ).astype(dtype)

    ones = lambda *shape: jnp.ones(shape, jnp.float32)  # noqa: E731

    def run(kind, n):
        ffn = {"ln2": ones(n, D), "w_gate": nrm((n, D, F), s),
               "w_up": nrm((n, D, F), s), "w_down": nrm((n, F, D), so)}
        if kind == ATTN:
            return dict(
                ffn, ln1=ones(n, D), wq=nrm((n, D, H, Dh), s),
                wkv=nrm((n, D, 2, G, Dh), s), wg=nrm((n, D, H, Dh), s),
                wo=nrm((n, H, Dh, D), so), qn=ones(n, Dh), kn=ones(n, Dh))
        return dict(
            ffn, ln1=ones(n, D), wq=nrm((n, D, Hl, Dl), s),
            wk=nrm((n, D, Hl, Dl), s), wv=nrm((n, D, Hl, Dl), s),
            wg=nrm((n, D, Hl, Dl), s), wo=nrm((n, Hl, Dl, D), so),
            qn=ones(n, Dl), kn=ones(n, Dl), on=ones(n, Hl * Dl))

    return {
        "wte": nrm((cfg.vocab_size, D), s),
        "runs": tuple(run(kind, n) for kind, n, _ in cfg.runs),
        "ln_f": ones(D),
        # muP divides the head's input by d_model / dim_model_base; the
        # head is drawn that much wider so seeded logits keep a spread
        "wlm": nrm((D, cfg.vocab_size), s * D / cfg.dim_model_base),
    }


def init_paged_cache(cfg: SalaConfig, num_pages: int, page_size: int,
                     num_slots: int) -> Dict:
    if page_size != cfg.block:
        raise ValueError(f"page_size must be the block, {cfg.block}")
    G, Dh = cfg.n_kv_heads, cfg.head_dim
    kv = (cfg.n_attn, num_pages, G, page_size, Dh)
    return {"k": jnp.zeros(kv, cfg.dtype), "v": jnp.zeros(kv, cfg.dtype),
            "kc": jnp.zeros((cfg.n_attn, num_pages, G, 4, Dh), jnp.float32),
            "state": jnp.zeros((cfg.n_lin, num_slots, cfg.lin_heads,
                                cfg.lin_head_dim, cfg.lin_head_dim),
                               jnp.float32)}


def lightning_slopes(n_heads: int):
    """log(1 / lambda_h) = 2^(-8 (h+1) / n_heads)."""
    return 2.0 ** (-8.0 * jnp.arange(1, n_heads + 1, dtype=jnp.float32)
                   / n_heads)


# ---------------------------------------------------------------------------
# Block selection (shared by the chunk and the tick)


def block_scores(q, kc, qpos, cfg: SalaConfig):
    """Each query's score of every block of its row, per KV group.

    q [N, G, R, Dh]; kc [N or 1, G, J, Dh] float32, the compressed keys
    in position order (kernel j covers tokens 16j .. 16j+31); qpos [N].
    Softmax over the kernels wholly at or before the query, summed over
    a group's heads; a block scores the maximum over the kernels that
    overlap it (a sum of probabilities, so 0 or more); the first blocks
    and the local window are forced (1e9); a block past the query's own
    scores -1.  Returns [N, G, J / 4] float32."""
    N, G, R, Dh = q.shape
    J = kc.shape[2]
    nb = J // 4
    s = jnp.einsum("ngrd,ngjd->ngrj", q.astype(jnp.float32), kc,
                   precision=_HI) * Dh ** -0.5
    ends = jnp.arange(J) * cfg.stride + cfg.kernel - 1
    ok = (ends[None, :] <= qpos[:, None])[:, None, None, :]
    m = jnp.max(jnp.where(ok, s, -jnp.inf), axis=-1, keepdims=True)
    e = jnp.where(ok, jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0)), 0.0)
    p = e / jnp.maximum(e.sum(-1, keepdims=True), 1e-30)
    g = p.sum(2)                                         # [N, G, J]
    own = g.reshape(N, G, nb, 4).max(-1)                 # kernels 4b..4b+3
    before = jnp.pad(g[..., 3::4], ((0, 0), (0, 0), (1, 0)))[..., :nb]
    score = jnp.maximum(own, before)                     # ...and 4b-1
    b = jnp.arange(nb)[None, :]
    bq = (qpos // cfg.block)[:, None]
    forced = (b < cfg.init_blocks) | ((b <= bq) & (b > bq - cfg.local_blocks))
    score = jnp.where(forced[:, None, :], 1e9, score)
    return jnp.where((b <= bq)[:, None, :], score, -1.0)


def select_blocks(q, kc, qpos, cfg: SalaConfig):
    """The `topk` blocks each query attends to, per KV group (arguments
    as `block_scores`): block ids [N, G, topk] by falling score, the
    forced blocks first.  What a tick gathers a row's pages by."""
    return lax.top_k(block_scores(q, kc, qpos, cfg),
                     cfg.topk)[1].astype(jnp.int32)


def chosen_blocks(score, topk: int):
    """score [..., nb] float32 -> [..., nb] bool: the set of blocks that
    `lax.top_k(score, topk)` names, found without sorting.  What a
    sparse chunk masks a query block's walk over the row's pages by.

    The `topk`-th largest score is found by bisection on the float32 bit
    pattern (made an integer of the same order), 32 passes of compare
    and count; a block is chosen if it scores more, or scores just that
    and is among the first of its equals that fill the set, which is
    `lax.top_k`'s own rule on ties (the lower index first)."""
    bits = lax.bitcast_convert_type(score, jnp.int32)
    key = bits ^ ((bits >> 31) & 0x7FFFFFFF)     # ordered as the floats are
    # (rows of scores: a [512, 2, 528] array is tiled two rows at a time,
    # and the passes over it took 0.58 ms on a v5e against 0.22 or less)
    key = key.reshape(-1, key.shape[-1])

    def bit(i, t):
        # t less the lowest integer, unsigned, gains its bits from the top
        # (the sum wraps)
        more = t + (jnp.int32(1) << (31 - i))
        return jnp.where((key >= more).sum(-1, keepdims=True) >= topk,
                         more, t)
    kth = lax.fori_loop(0, 32, bit,
                        jnp.full((key.shape[0], 1), -2 ** 31, jnp.int32))
    above, ties = key > kth, key == kth
    room = topk - above.sum(-1, keepdims=True)
    chosen = above | (ties & (jnp.cumsum(ties, axis=-1) <= room))
    return chosen.reshape(score.shape)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _softmax_attend(q, k, v, mask, dt):
    """q [..., R, Dh], k/v [..., S, Dh], mask [..., S] -> [..., R, Dh]."""
    s = jnp.einsum("...rd,...sd->...rs", q, k,
                   preferred_element_type=jnp.float32) * q.shape[-1] ** -0.5
    s = jnp.where(mask[..., None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("...rs,...sd->...rd", p.astype(dt), v)


# ---------------------------------------------------------------------------
# Layers, for a single-row chunk of T tokens (x [T, D]) and for a tick of
# B rows (x [B, D]).  `li` indexes the layer among the layers of its kind.


def _attn_project(lp, x, cfg):
    dt = cfg.dtype
    h = _rmsnorm(x, lp["ln1"])
    q = jnp.einsum("td,dhk->thk", h, lp["wq"].astype(dt))
    kv = jnp.einsum("td,dchk->tchk", h, lp["wkv"].astype(dt))
    gate = jax.nn.sigmoid(jnp.einsum("td,dhk->thk", h, lp["wg"].astype(dt)))
    return (_rmsnorm(q, lp["qn"]), _rmsnorm(kv[:, 0], lp["kn"]), kv[:, 1],
            gate)


def _attn_close(lp, x, out, gate, cfg):
    out = jnp.einsum("thk,hkd->td", out * gate, lp["wo"].astype(cfg.dtype))
    return x + (cfg.res_scale * out).astype(x.dtype)


def _ffn(lp, x, cfg):
    out = _swiglu(lp, _rmsnorm(x, lp["ln2"]), cfg.dtype)
    return x + (cfg.res_scale * out).astype(x.dtype)


def _attn_chunk(lp, x, li, cache, bt, start, cfg):
    T = x.shape[0]
    G, Dh, psz = cfg.n_kv_heads, cfg.head_dim, cfg.block
    R = cfg.n_heads // G
    nblk = bt.shape[0]
    dt = cfg.dtype
    q, k, v, gate = _attn_project(lp, x, cfg)
    cols = start + jnp.arange(T)

    # whole pages in, a KV group's keys contiguous
    pages = lax.dynamic_slice(bt, (start // psz,), (T // psz,))
    paged = lambda a: a.reshape(T // psz, psz, G, Dh).swapaxes(1, 2)  # noqa: E731,E501
    ck = cache["k"].at[li, pages].set(paged(k))
    cv = cache["v"].at[li, pages].set(paged(v))

    # the kernels that end inside this chunk: starts start-16, start, ...
    prev_page = bt[jnp.maximum(start // psz - 1, 0)]
    prev = ck[li, prev_page, :, psz - cfg.stride:]       # [G, 16, Dh]
    window = jnp.concatenate([prev.swapaxes(0, 1), k]).astype(jnp.float32)
    halves = window.reshape(T // cfg.stride + 1, cfg.stride, G, Dh).mean(1)
    kern = 0.5 * (halves[:-1] + halves[1:])              # [T/16, G, Dh]
    js = start // cfg.stride - 1 + jnp.arange(T // cfg.stride)
    kpages = jnp.where(js >= 0, bt[jnp.maximum(js, 0) // 4], 0)
    garange = jnp.arange(G)
    ckc = cache["kc"].at[li, kpages[:, None], garange[None, :],
                         (js % 4)[:, None]].set(kern)

    qg = q.reshape(T, G, R, Dh)

    def shared(q_, cols_, pages, first_pos):
        """Queries [n, G, R, Dh] over whole pages that all of them read
        (keys in position order from `first_pos` of each page): scores
        [n, G, R, keys] float32, causally masked, and the values."""
        ks = ck[li, pages].swapaxes(0, 1).reshape(G, -1, Dh)
        vs = cv[li, pages].swapaxes(0, 1).reshape(G, -1, Dh)
        s = jnp.einsum("tgrd,gsd->tgrs", q_, ks,
                       preferred_element_type=jnp.float32) * Dh ** -0.5
        keypos = (first_pos[:, None] + jnp.arange(psz)).reshape(-1)
        seen = keypos[None, :] <= cols_[:, None]
        return jnp.where(seen[:, None, None, :], s, -jnp.inf), vs

    def dense(npg):
        """Causal attention over the row's first `npg` pages, in spans
        of _DENSE_SPAN_KEYS keys whose softmax parts are merged by their
        maxima and sums (one softmax over 8,192 keys compiles to a
        program six times slower than two over 4,096: PERF.md §6)."""
        span = max(1, _DENSE_SPAN_KEYS // psz)
        spans = [(a, min(a + span, npg)) for a in range(0, npg, span)]

        def branch(_):
            qb = min(T, 128)

            def block(args):
                qq, cc = args                            # [qb, G, R, Dh]
                parts = []
                for a, b in spans:
                    s, vs = shared(qq, cc, bt[a:b], jnp.arange(a, b) * psz)
                    m = s.max(-1, keepdims=True)
                    m = jnp.where(jnp.isfinite(m), m, 0.0)
                    e = jnp.exp(s - m)
                    parts.append((m, e.sum(-1, keepdims=True), jnp.einsum(
                        "tgrs,gsd->tgrd", e.astype(dt), vs,
                        preferred_element_type=jnp.float32)))
                top = functools.reduce(jnp.maximum, [m for m, _, _ in parts])
                total = sum(w * jnp.exp(m - top) for m, w, _ in parts)
                out = sum(o * jnp.exp(m - top) for m, _, o in parts)
                return (out / total).astype(dt)
            out = lax.map(block, (qg.reshape(T // qb, qb, G, R, Dh),
                                  cols.reshape(T // qb, qb)))
            return out.reshape(T, G * R, Dh)
        return branch

    def sparse(_):
        """Block-sparse attention a QUERY BLOCK (one page of the chunk)
        at a time.  The selection hands over a mask and sorts nothing:
        `chosen_blocks` of the scores `select_blocks` would sort; one
        kernel a layer (ops/paged_prefill_attention.py) then walks the
        row's pages once a query block and scores its 64 tokens x the
        group's heads against each page under that mask (in the block's
        own page: the keys up to the token), one softmax over all of it:
        the forced blocks are pages like the others."""
        kcg = ckc[li, bt].swapaxes(0, 1).reshape(1, G, nblk * 4, Dh)
        with jax.named_scope("sparse_score"):
            score = lax.map(
                lambda a: block_scores(a[0], kcg, a[1], cfg),
                (qg.reshape(T // psz, psz, G, R, Dh),
                 cols.reshape(T // psz, psz))).reshape(T, G, nblk)
            chosen = chosen_blocks(score, cfg.topk)
        with jax.named_scope("sparse_attend"):
            # keys of each page a token sees: its own page up to itself
            seen = jnp.clip(cols[:, None] + 1 - jnp.arange(nblk) * psz,
                            0, psz)
            visible = jnp.where(chosen, seen[:, None, :], 0)
            return query_block_attention(q, ck, cv, li, bt, visible,
                                         interpret=not _on_tpu())

    # Below dense_len the chunk attends to the row's first pages, as many
    # as a power-of-two bucket that holds the chunk's last token.
    most = min(cfg.dense_len // psz, nblk)
    buckets = sorted({min(most, max(T // psz, most >> i)) for i in range(4)})
    need = (start + T) // psz
    which = sum((need > b).astype(jnp.int32) for b in buckets[:-1])
    which = jnp.where(start >= cfg.dense_len, len(buckets), which)
    out = lax.switch(which, [dense(b) for b in buckets] + [sparse], None)
    cache = dict(cache, k=ck, v=cv, kc=ckc)
    return _attn_close(lp, x, out, gate, cfg), cache


def _attn_tick(lp, x, li, cache, bt, pos, cfg):
    B = x.shape[0]
    G, Dh, psz = cfg.n_kv_heads, cfg.head_dim, cfg.block
    R = cfg.n_heads // G
    nblk = bt.shape[1]
    dt = cfg.dtype
    q, k, v, gate = _attn_project(lp, x, cfg)
    at = lambda idx: jnp.take_along_axis(bt, idx, axis=1)  # noqa: E731

    # Every index of a write or a small read names its KV group: a
    # slice over the groups between two index arrays makes the compiler
    # re-lay the whole pool around it (0.55 GB a tick and layer).
    garange = jnp.arange(G)
    page = at((pos // psz)[:, None])
    off = (pos % psz)[:, None]
    ck = cache["k"].at[li, page, garange[None, :], off].set(k)
    cv = cache["v"].at[li, page, garange[None, :], off].set(v)

    # the kernel that ends at this position, from the pages
    done = (pos % cfg.stride == cfg.stride - 1) & (pos >= cfg.kernel - 1)
    kp = jnp.maximum(pos[:, None] - (cfg.kernel - 1)
                     + jnp.arange(cfg.kernel), 0)
    keys = ck[li, at(kp // psz)[..., None], garange[None, None, :],
              (kp % psz)[..., None]]                     # [B, 32, G, Dh]
    kern = keys.astype(jnp.float32).mean(1)
    j = jnp.maximum(pos - (cfg.kernel - 1), 0) // cfg.stride
    jpage = jnp.where(done[:, None], at((j // 4)[:, None]), 0)
    ckc = cache["kc"].at[li, jpage, garange[None, :],
                         (j % 4)[:, None]].set(kern)

    qg = q.reshape(B, G, R, Dh)
    with jax.named_scope("sparse_score"):
        kcg = ckc[li, bt].swapaxes(1, 2).reshape(B, G, nblk * 4, Dh)
        chosen = select_blocks(qg, kcg, pos, cfg)        # [B, G, topk]
    with jax.named_scope("sparse_attend"):
        npg = max(min(cfg.dense_len // psz, nblk), cfg.topk)
        is_sparse = (pos >= cfg.dense_len)[:, None, None]
        slots = jnp.arange(npg)[None, None, :]
        blk = jnp.where(is_sparse,
                        jnp.pad(chosen, ((0, 0), (0, 0),
                                         (0, npg - cfg.topk))), slots)
        live = jnp.where(is_sparse, slots < cfg.topk, True)
        pg = jnp.take_along_axis(bt[:, None, :], blk, axis=2)
        kb = ck[li, pg, garange[None, :, None]].reshape(B, G, npg * psz, Dh)
        vb = cv[li, pg, garange[None, :, None]].reshape(B, G, npg * psz, Dh)
        keypos = blk[..., None] * psz + jnp.arange(psz)
        mask = (live[..., None] & (keypos <= pos[:, None, None, None])
                ).reshape(B, G, npg * psz)
        out = _softmax_attend(qg, kb, vb, mask, dt).reshape(B, G * R, Dh)
    cache = dict(cache, k=ck, v=cv, kc=ckc)
    return _attn_close(lp, x, out, gate, cfg), cache


def _lin_project(lp, x, positions, cfg):
    """x [n, D], each token at its own position [n] (a chunk's run of
    positions, or a tick's one position a row)."""
    dt = cfg.dtype
    h = _rmsnorm(x, lp["ln1"])
    proj = lambda w: jnp.einsum("td,dhk->thk", h, w.astype(dt))  # noqa: E731
    rope = lambda a: _rope_at(a[None], positions[None],  # noqa: E731
                              cfg.rope_theta)[0]
    q = rope(_rmsnorm(proj(lp["wq"]), lp["qn"]))
    k = rope(_rmsnorm(proj(lp["wk"]), lp["kn"]))
    return q, k, proj(lp["wv"]), jax.nn.sigmoid(proj(lp["wg"]))


def _lin_close(lp, x, o, gate, cfg):
    n, Hl, Dl = o.shape
    o = _rmsnorm(o.reshape(n, Hl * Dl).astype(cfg.dtype), lp["on"])
    return _attn_close(lp, x, o.reshape(n, Hl, Dl), gate, cfg)


def lightning_chunked(q, k, v, S0, ok, cfg: SalaConfig, sub: int = 128):
    """Linear attention with per-head decay over a chunk, in sub-chunks:
    S_t = lambda S_{t-1} + k_t^T v_t, o_t = scale q_t S_t.  q/k/v
    [T, H, D], S0 [H, D, D] float32, ok [T] marks the tokens that count
    (a pad neither decays nor adds).  Returns (o [T, H, D] float32,
    S after the last counted token)."""
    T, H, D = q.shape
    C = sub if T % sub == 0 else T
    scale = D ** -0.5
    a = -lightning_slopes(H)[None, :] * ok[:, None]       # log decay [T, H]
    k = jnp.where(ok[:, None, None], k, 0)
    cut = lambda x: x.reshape((T // C, C) + x.shape[1:])  # noqa: E731
    causal = jnp.tril(jnp.ones((C, C), bool))

    def body(S, inp):
        qc, kc, vc, ac = inp
        A = jnp.cumsum(ac, axis=0).T                      # [H, C] inclusive
        qk = jnp.einsum("ihd,jhd->hij", qc, kc,
                        preferred_element_type=jnp.float32) * scale
        decay = jnp.where(causal, jnp.exp(jnp.where(
            causal, A[:, :, None] - A[:, None, :], 0.0)), 0.0)
        intra = jnp.einsum("hij,jhd->ihd", (qk * decay).astype(vc.dtype),
                           vc, preferred_element_type=jnp.float32)
        qd = qc.astype(jnp.float32) * (scale * jnp.exp(A).T[:, :, None])
        inter = jnp.einsum("ihd,hde->ihe", qd, S, precision=_HI)
        kd = kc.astype(jnp.float32) * jnp.exp(A[:, -1:] - A).T[:, :, None]
        S = jnp.exp(A[:, -1])[:, None, None] * S + jnp.einsum(
            "jhd,jhe->hde", kd, vc.astype(jnp.float32), precision=_HI)
        return S, intra + inter

    S, o = lax.scan(body, S0, (cut(q), cut(k), cut(v), cut(a)))
    return o.reshape(T, H, D), S


def _lin_chunk(lp, x, li, cache, start, slot, valid, cfg):
    T = x.shape[0]
    q, k, v, gate = _lin_project(lp, x, start + jnp.arange(T), cfg)
    with jax.named_scope("lightning_chunk"):
        S0 = cache["state"][li, slot]
        S0 = jnp.where(start == 0, 0.0, S0)               # a row begins
        o, S = lightning_chunked(q, k, v, S0, jnp.arange(T) < valid, cfg)
        cache = dict(cache, state=cache["state"].at[li, slot].set(S))
    return _lin_close(lp, x, o, gate, cfg), cache


def _lin_tick(lp, x, li, cache, pos, cfg):
    q, k, v, gate = _lin_project(lp, x, pos, cfg)
    with jax.named_scope("lightning_step"):
        active = (pos > 0)[:, None]
        lam = jnp.where(active, jnp.exp(-lightning_slopes(q.shape[1]))[None],
                        1.0)
        kf = jnp.where(active[..., None], k, 0).astype(jnp.float32)
        S = lam[..., None, None] * cache["state"][li] \
            + kf[..., :, None] * v.astype(jnp.float32)[..., None, :]
        qs = q.astype(jnp.float32) * q.shape[-1] ** -0.5
        o = (qs[..., :, None] * S).sum(-2)
        cache = dict(cache, state=cache["state"].at[li].set(S))
    return _lin_close(lp, x, o, gate, cfg), cache


# ---------------------------------------------------------------------------
# The paged step


def _through_layers(params, x, cache, cfg, attn, lin):
    """x through every layer in order: a run of equal layers is one
    scan over its stack, the cache rides as the carry (updated in place
    at [layer], as decode.paged_chunk_step does for its pool)."""
    def one(kind):
        mixer = attn if kind == ATTN else lin

        def layer(carry, inputs):
            x, cache = carry
            lp, li = inputs
            x, cache = mixer(lp, x, li, cache)
            return (_ffn(lp, x, cfg), cache), None
        return layer

    for (kind, n, i0), stack in zip(cfg.runs, params["runs"]):
        (x, cache), _ = lax.scan(one(kind), (x, cache),
                                 (stack, i0 + jnp.arange(n)))
    return x, cache


def _logits(params, x, cfg):
    x = _rmsnorm(x, params["ln_f"]) / (cfg.d_model / cfg.dim_model_base)
    return jnp.einsum("td,dv->tv", x.astype(cfg.dtype),
                      params["wlm"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32)


def _embed(params, tokens, cfg):
    return (cfg.scale_emb * jnp.take(params["wte"], tokens, axis=0)
            ).astype(cfg.dtype)


def paged_chunk_step(params: Dict, tokens, pos, cache: Dict, block_tables,
                     cfg: SalaConfig, pad_lo=None, slot=None, valid=None
                     ) -> Tuple[Any, Dict]:
    """The model's paged step, under decode.paged_chunk_step's contract.

    `pos` a scalar: ONE row's chunk of T tokens starting there (T and
    `pos` whole blocks) — single-row prefill.  It fills the row's pages
    and compressed keys and carries the recurrent state of decode row
    `slot` (default 0), zeroing it first when `pos` is 0; only the first
    `valid` tokens (default all) move the state.  `pos` a [B] vector
    with one token a row: the decode tick.  Rows at position 0 are idle:
    their writes land wherever their block table points (the trash
    page) and their state stays as it is.
    Returns (logits [B, t, V] float32, cache)."""
    if pad_lo is not None:
        raise NotImplementedError("left-padded rows")
    B, t = tokens.shape
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        if B != 1 or t % cfg.block:
            raise ValueError(f"a chunk is one row of whole blocks of "
                             f"{cfg.block} tokens, got {tokens.shape}")
        slot = jnp.int32(0) if slot is None else jnp.asarray(slot, jnp.int32)
        valid = jnp.int32(t) if valid is None \
            else jnp.asarray(valid, jnp.int32)
        bt = block_tables[0]
        x, cache = _through_layers(
            params, _embed(params, tokens[0], cfg), cache, cfg,
            lambda lp, x, li, c: _attn_chunk(lp, x, li, c, bt, pos, cfg),
            lambda lp, x, li, c: _lin_chunk(lp, x, li, c, pos, slot, valid,
                                            cfg))
        return _logits(params, x, cfg)[None], cache
    if t != 1:
        raise NotImplementedError(
            "several tokens a row at per-row positions (speculative "
            "verify) need the recurrent state rolled back on rejection")
    x, cache = _through_layers(
        params, _embed(params, tokens[:, 0], cfg), cache, cfg,
        lambda lp, x, li, c: _attn_tick(lp, x, li, c, block_tables, pos,
                                        cfg),
        lambda lp, x, li, c: _lin_tick(lp, x, li, c, pos, cfg))
    return _logits(params, x, cfg)[:, None], cache


BODY = PagedBody(
    init_paged_cache=init_paged_cache, paged_chunk_step=paged_chunk_step,
    check_paging=check_paging, attn_keys=attn_keys,
    chunk_selects=chunk_selects, page_keys=("k", "v", "kc"),
    row_state_keys=("state",), n_attn=lambda cfg: cfg.n_attn)
