"""Autoregressive decoding with a KV cache for the flagship models.

The reference delegates inference to user frameworks (vLLM/torch); here
the model layer is ours, so serving-side decode is part of the
framework.  TPU-native design constraints drive the shape of this
module:

  * static shapes everywhere — the cache is a fixed [L, B, max_seq,
    Hkv, Dh] buffer updated with lax.dynamic_update_slice, and the
    per-step attention masks positions > pos instead of slicing, so one
    XLA compilation serves the whole generation;
  * the decode loop is a lax.scan (one dispatch for the whole
    generation, not one per token — dispatch latency dominates
    single-token steps);
  * GQA caches stay at Hkv size (the memory saving is the point of
    GQA); query-head groups are expanded at the attention einsum.

Single-device path (serve replicas own one chip); the training-side
mesh machinery (models/gpt.py) is unchanged.  Supports GPT (learned
positions, fused QKV) and LLaMA (RoPE, GQA, SwiGLU).
"""

from __future__ import annotations

import dataclasses
import functools
import operator
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.models import llama as llama_mod
from ray_tpu.models.gpt import GPTConfig, _rmsnorm


# ---------------------------------------------------------------------------
# Arch adapters: how each model family embeds tokens and builds q/k/v/ffn.


def _is_llama(cfg) -> bool:
    """Which adapter the DENSE body below uses (llama: RoPE, GQA, SwiGLU;
    else GPT).  Not the model switch: see paged_body."""
    return isinstance(cfg, llama_mod.LlamaConfig)


# ---------------------------------------------------------------------------
# The seam between the serving engine and a model body


def _selects_no_pages(cfg, start: int) -> bool:
    return False


@dataclasses.dataclass(frozen=True)
class PagedBody:
    """What a model body owes the serving engine (serve/llm/engine.py,
    kv_tier.py, kv_transfer.py), declared once by the body's module and
    named by its config as `cfg.paged_body`; `paged_body(cfg)` below is
    how everyone finds it, and DENSE_BODY is this file's own.  Every
    default is written here and nowhere else.  `cfg` is the config that
    named the body; the host-side counters are given `pos`, the active
    rows' positions, and `last`, the last column of the call for EVERY
    decode row (idle ones at 0)."""
    # (cfg, num_pages, page_size, num_slots) -> `engine._cache`, one
    # pytree: the pool and, where the body has it, state per decode row
    init_paged_cache: Callable[..., Dict]
    # under the contract of `paged_chunk_step` below
    paged_chunk_step: Callable[..., Tuple[Any, Dict]]
    # (cfg, *, page_size, prefill_chunk, speculate_k): raises for paging
    # the body's layout cannot serve
    check_paging: Callable[..., None]
    # (cfg, pos) -> (keys read, keys held) by a tick's rows
    attn_keys: Callable[..., Tuple[int, int]]
    # (cfg, start) -> whether the prefill chunk at `start` selects pages
    chunk_selects: Callable[..., bool] = _selects_no_pages
    # the cache entries that are the pool: a page's bytes are theirs
    page_keys: Tuple[str, ...] = ("k", "v")
    # ...and those that are state of a decode row.  A body that names
    # any HAS ROW STATE: what treats a page as the whole of a sequence's
    # state refuses it (kv_tier.refuse_row_state)
    row_state_keys: Tuple[str, ...] = ()
    # the pool is `k` and `v` of [L, P, page, Hkv, Dh], all a sequence
    # keeps: tiers, kv_export / kv_import, migration and sessions can
    # frame a page (kv_tier.refuse_unframed refuses a body that is not)
    framed: bool = False
    # the single-row chunk takes `slot` (the decode row it fills) and
    # `valid` (its real tokens); a body that takes neither is given
    # neither, and lowers to the program it always was
    chunk_takes_row: bool = True
    # (cfg) -> how many layers attend, of a body that mixes in others
    n_attn: Callable[..., int] = operator.attrgetter("n_layers")
    # (cfg, last, page_size, nblk) -> keys the call pulled from the
    # cache; None: no more than it reads (`keys_gathered`).  A call that
    # is not the body's decode step (a verify of t tokens a row, which
    # only a body that speculates takes) hands it `t` too
    attn_keys_gathered: Optional[Callable[..., int]] = None
    # (cfg, pos, last, page_size, nblk) -> (gathered, held) in the paged
    # layers alone, of a body whose layers are not all paged
    attn_keys_paged: Optional[Callable[..., Tuple[int, int]]] = None
    # (cache) -> a copy of the device counters on its way to the host,
    # taken behind a tick; (that copy, cfg) -> {name: number} for
    # engine.stats().  None, both: nothing is counted on the device
    snapshot_counters: Optional[Callable[..., Any]] = None
    read_counters: Optional[Callable[..., Dict[str, Any]]] = None
    # the columns a decode step takes of a row: 1, a token a row a tick,
    # for every body but one that generates by diffusion over BLOCKS of
    # so many positions (models/sdar_moe.py), whose step takes a row's
    # whole block, fixes some of its positions and yields tokens only
    # when a block is full (engine._paged_block_step); `mask_token` is
    # the id that stands in a position not fixed yet (None at block 1)
    block: int = 1
    mask_token: Optional[int] = None

    @property
    def has_row_state(self) -> bool:
        return bool(self.row_state_keys)

    def keys_gathered(self, cfg, read, last, page_size, nblk,
                      t: int = 1) -> int:
        """Keys a call of `t` tokens a row pulled from the cache to read
        `read` of them."""
        if self.attn_keys_gathered is None:
            return read
        if t == self.block:
            return self.attn_keys_gathered(cfg, last, page_size, nblk)
        return self.attn_keys_gathered(cfg, last, page_size, nblk, t)


def paged_body(cfg) -> PagedBody:
    """The body that runs `cfg` through a paged cache: this file's dense
    one for the two dense configs, else the one the config names
    (minicpm_sala.py, deepseek_v2.py, exaone_moe.py, jamba.py, zaya.py,
    sdar_moe.py, bailing_hybrid.py, glm_moe_dsa.py; mimo_v2_flash.py
    names exaone_moe's).  Never None."""
    if isinstance(cfg, (GPTConfig, llama_mod.LlamaConfig)):
        return DENSE_BODY
    return cfg.paged_body


def _kv_heads(cfg) -> int:
    return cfg.n_kv_heads if _is_llama(cfg) else cfg.n_heads


def _rope_at(x, positions, theta: float):
    """RoPE with PER-ROW positions [B, t] (left-padded batches put the
    same logical position at different columns per row; llama.py's
    _rope takes one scalar offset for the whole batch)."""
    b, t, h, d = x.shape
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    pos = positions.astype(jnp.float32)[:, :, None] * freqs[None, None]
    cos = jnp.cos(pos)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(pos)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], axis=-1)


def _embed(params, tokens, positions, cfg):
    """tokens [B, t] at per-row logical positions [B, t]."""
    x = jnp.take(params["wte"], tokens, axis=0)
    if not _is_llama(cfg):
        x = x + jnp.take(params["wpe"], positions, axis=0)
    return x.astype(cfg.dtype)


def _qkv(lp, h, positions, cfg):
    """h [B, t, D] -> q [B,t,H,Dh], k/v [B,t,Hkv,Dh] (RoPE applied at
    per-row logical positions for llama)."""
    dt = cfg.dtype
    if _is_llama(cfg):
        q = jnp.einsum("btd,dhk->bthk", h, lp["wq"].astype(dt))
        kv = jnp.einsum("btd,dchk->btchk", h, lp["wkv"].astype(dt))
        k, v = kv[:, :, 0], kv[:, :, 1]
        q = _rope_at(q, positions, cfg.rope_theta)
        k = _rope_at(k, positions, cfg.rope_theta)
        return q, k, v
    qkv = jnp.einsum("btd,dchk->btchk", h, lp["wqkv"].astype(dt))
    return qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]


def _swiglu(lp, h, dt):
    """The SwiGLU feed-forward of a normed h [..., D] (no residual)."""
    g = jax.nn.silu(jnp.einsum("...d,df->...f", h, lp["w_gate"].astype(dt)))
    u = jnp.einsum("...d,df->...f", h, lp["w_up"].astype(dt))
    return jnp.einsum("...f,fd->...d", g * u, lp["w_down"].astype(dt))


def _ffn(lp, x, cfg):
    dt = cfg.dtype
    h = _rmsnorm(x, lp["ln2"])
    if _is_llama(cfg):
        return x + _swiglu(lp, h, dt)
    hh = jax.nn.gelu(jnp.einsum("btd,df->btf", h, lp["w1"].astype(dt)))
    return x + jnp.einsum("btf,fd->btd", hh, lp["w2"].astype(dt))


def _attn_out(lp, out, cfg):
    return jnp.einsum("bthk,hkd->btd", out, lp["wo"].astype(cfg.dtype))


def _final_logits(params, x, cfg):
    x = _rmsnorm(x, params["ln_f"])
    return jnp.einsum("btd,dv->btv", x.astype(cfg.dtype),
                      params["wlm"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Cache


def init_cache(cfg, batch: int, max_seq: Optional[int] = None) -> Dict:
    """Fixed-shape KV cache: k/v [L, B, S, Hkv, Dh] in cfg.dtype."""
    S = max_seq or cfg.max_seq
    shape = (cfg.n_layers, batch, S, _kv_heads(cfg), cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype)}


@functools.partial(jax.jit, donate_argnums=(0,))
def reset_cache_slot(cache: Dict, slot) -> Dict:
    """Zero one batch row of the cache (slot recycling: when the
    continuous-batching engine evicts a finished request, its slot is
    wiped so the next occupant starts from the documented all-zeros
    state).  `slot` is a traced scalar — one compilation serves every
    slot index."""
    L, B, S, H, D = cache["k"].shape
    z = jnp.zeros((L, 1, S, H, D), cache["k"].dtype)
    return {"k": lax.dynamic_update_slice(
                cache["k"], z, (0, slot, 0, 0, 0)),
            "v": lax.dynamic_update_slice(
                cache["v"], z, (0, slot, 0, 0, 0))}


@functools.partial(jax.jit, donate_argnums=(0,))
def insert_cache_slot(cache: Dict, row_cache: Dict, slot) -> Dict:
    """Copy batch row 0 of `row_cache` (a batch-1 cache filled by
    prefill/chunk_step) into batch row `slot` of `cache` — continuous-
    batching admission: a request prefilled off to the side joins the
    decode batch without touching any other row.  Sequence widths must
    match; `slot` is a traced scalar (single compilation)."""
    return {"k": lax.dynamic_update_slice(
                cache["k"], row_cache["k"][:, :1], (0, slot, 0, 0, 0)),
            "v": lax.dynamic_update_slice(
                cache["v"], row_cache["v"][:, :1], (0, slot, 0, 0, 0))}


def init_paged_cache(cfg, num_pages: int, page_size: int,
                     num_slots: Optional[int] = None) -> Dict:
    """The cache `cfg`'s body declares.  (The serve engine reserves page
    0 as a trash page for inactive rows' writes; no initializer cares.)"""
    return paged_body(cfg).init_paged_cache(cfg, num_pages, page_size,
                                            num_slots)


def _dense_paged_cache(cfg, num_pages: int, page_size: int,
                       num_slots: Optional[int] = None) -> Dict:
    """Paged KV pool: k/v [L, P, page_size, Hkv, Dh] in cfg.dtype.

    Rows of a batch don't own contiguous cache rows here — each row owns
    a BLOCK TABLE of page ids, and attention gathers its keys/values
    through the table (vLLM's PagedAttention layout, expressed in the
    same masked static-shape style as the contiguous cache: gather
    spans of pages up to the width the rows hold, mask columns past the
    row's position).

    All layers live in ONE array per tensor, and the steps that use the
    pool never take a layer out of it: _dense_chunk_step indexes it at
    [l, pages] inside its layer scan (see there), page import/export at
    [:, pages].  On a chip the pool is gigabytes, and a step that
    formed cache["k"][l] would move a layer's worth of it per layer."""
    shape = (cfg.n_layers, num_pages, page_size, _kv_heads(cfg),
             cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype)}


@functools.partial(jax.jit, donate_argnums=(0,))
def paged_write_pages(cache: Dict, page_ids, k_pages, v_pages) -> Dict:
    """Splice imported K/V pages into the pool: k_pages/v_pages
    [n, L, page_size, Hkv, Dh] (page-major — each page's bytes travel
    the wire as one contiguous buffer) land at pool rows `page_ids`
    [n].  One scatter per cache tensor, cache donated: a KV migration
    commits between decode ticks as a single dispatch, never a
    reallocation or a tick stall."""
    return {"k": cache["k"].at[:, page_ids].set(
                jnp.moveaxis(k_pages, 0, 1).astype(cache["k"].dtype)),
            "v": cache["v"].at[:, page_ids].set(
                jnp.moveaxis(v_pages, 0, 1).astype(cache["v"].dtype))}


@jax.jit
def paged_read_pages(cache: Dict, page_ids) -> Any:
    """Gather pool rows `page_ids` [n] as ONE page-major stack
    [n, 2, L, page_size, Hkv, Dh]: row i is page i's K then its V, so
    on the host its bytes ARE the page's at-rest and wire frame
    (kv_tier.page_frame) with no copy to build it.  Compiled per length
    of `page_ids`: callers go through paged_read_stack, which only
    ever asks for paged_read_batch(cache) ids."""
    return jnp.stack([jnp.moveaxis(cache["k"][:, page_ids], 0, 1),
                      jnp.moveaxis(cache["v"][:, page_ids], 0, 1)], axis=1)


# Bytes of K and V one dispatch of paged_read_pages gathers: large
# enough that a pressure demotion of some tens of pages is one or two
# dispatches, small enough that its device-to-host copy does not hold
# the transfer queue for long ahead of a tick's sampled tokens.
_READ_BYTES = 32 << 20


def paged_read_batch(cache: Dict) -> int:
    """Page ids one dispatch of paged_read_pages takes, from the page's
    bytes alone: _READ_BYTES of K and V, between 8 and 64 pages (32 for
    a 1 MiB page).  ONE size for a pool's whole life, so the program
    compiles once, in the first pass that reads a page.  A page's bytes
    are both arrays' (a value may be narrower than a key)."""
    page = sum(cache[n].size * cache[n].dtype.itemsize
               for n in ("k", "v")) // cache["k"].shape[1]
    return max(8, min(64, _READ_BYTES // page))


def paged_read_stack(cache: Dict, page_ids) -> Any:
    """Dispatch ONE gather of up to paged_read_batch(cache) page ids in
    the one compiled shape and return its device stack WITHOUT waiting
    for it: the ids are padded with the last one repeated, and whoever
    copies the stack to the host drops the rows past len(page_ids).
    The pool is an input of the gather and the device runs programs in
    order, so a step dispatched afterwards that rewrites these pages
    (through the donated cache) writes after the gather has read them."""
    ids = np.asarray(page_ids, np.int32)
    pad = paged_read_batch(cache) - len(ids)
    if pad < 0:
        raise ValueError(f"{len(ids)} page ids for a stack of "
                         f"{len(ids) + pad}")
    if pad:
        ids = np.concatenate([ids, np.full(pad, ids[-1], np.int32)])
    return paged_read_pages(cache, ids)


def paged_read_pages_host(cache: Dict, page_ids, before_dispatch=None
                          ) -> Tuple[Any, Any]:
    """The gather of `page_ids` (any count: ceil(n / paged_read_batch)
    stacks) + the host landing, BLOCKING: page-major numpy K and V
    stacks [n, L, page_size, Hkv, Dh] for a caller that needs the bytes
    now (migration export).  The same compiled program and the same
    bytes as the tier demotion's landing, which does not wait (the
    engine hands each dispatched stack to its lander thread), so what a
    tier holds can never diverge from what the wire ships.
    `before_dispatch`, where given, is called before each stack leaves
    (the engine's capture log marks its dispatches with it)."""
    size = paged_read_batch(cache)
    parts = [page_ids[lo:lo + size] for lo in range(0, len(page_ids), size)]
    stacks = []
    for part in parts:
        if before_dispatch:
            before_dispatch()
        stacks.append(paged_read_stack(cache, part))
    kv = np.concatenate([np.asarray(stack)[:len(part)]
                         for stack, part in zip(stacks, parts)])
    return kv[:, 0], kv[:, 1]


# What sizes a span of the dense paged step's attention where it walks
# spans (paged_span_blocks: a chunk, a verify, a tick where there is no
# TPU): the K it gathers for every row of a call, in
# bytes, and its width in columns.  A turn of the span loop costs ~5 us
# beside its bytes and a call reads up to one span past its deepest row,
# so a span is as wide as the gathered K and V stay cheap to hold and
# no wider than keeps a single row's chunk close to what it has cached.
# Measured on a v5e (PERF.md section 6, PR 29): 16-row ticks are fastest
# at 8 MiB (4 and 16 MiB: +5 %, +16 % at rows ~700 deep), a one-row
# chunk under 1,024 columns at 512 columns (2,048: +2 %).
_SPAN_BYTES = 8 << 20
_SPAN_COLS = 512


def paged_span_blocks(key_bytes: int, psz: int, nblk: int) -> int:
    """How many consecutive block-table entries one span of the dense
    paged step's attention covers, from the shapes alone (`key_bytes`:
    one page of `psz` tokens' K for all rows of the call): the pages
    whose gathered K is _SPAN_BYTES, at most _SPAN_COLS columns and at
    most the whole table.  A 16-row tick over 16-token pages of 8 x 128
    bf16 heads walks 16 blocks (256 columns) a span, a single-row chunk
    32 blocks.  Of the DENSE pool only (one head count and width for
    every layer): a model with its own paged step sizes its own spans.
    A tick on a TPU walks no span (`_reads_own_pages`): each row's own
    blocks are `ops/paged_attention.block_pages`'s."""
    return max(1, min(nblk, _SPAN_BYTES // key_bytes, _SPAN_COLS // psz))


def _dense_check_paging(cfg, **paging) -> None:
    if not _is_llama(cfg) and cfg.n_experts:
        raise NotImplementedError(
            "continuous batching runs the dense body for dense models "
            "only (it has no expert layer; a model that routes brings "
            "its own paged step)")


def _dense_attn_keys(cfg, pos) -> Tuple[int, int]:
    """(keys read, keys held): all a row holds, in every layer."""
    keys = (int(pos.sum()) + len(pos)) * cfg.n_layers
    return keys, keys


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _reads_own_pages(t: int, head_dim: int) -> bool:
    """Does a per-row call of `t` tokens a row read each row's own pages
    through `ops/paged_attention.py`?  The tick (one token a row) on a
    TPU, of heads that fill the chip's 128 lanes (its compiler refuses
    the copy of a narrower page: "must be aligned to tiling (128)");
    every other call walks spans."""
    return t == 1 and head_dim % 128 == 0 and _on_tpu()


def _dense_attn_keys_gathered(cfg, last, page_size: int, nblk: int,
                              t: int = 1) -> int:
    """Keys one call of `t` tokens a row pulls from the pool.  A tick on
    a TPU: each live row's own blocks of pages, what the kernel copies
    (an idle row, at 0: none).  Every other call: for EVERY row whole
    spans (of paged_span_blocks entries) up to the deepest row's last
    column, the trip count _dense_chunk_step reads from the same
    positions."""
    rows = len(last)
    key = _kv_heads(cfg) * cfg.head_dim * jnp.dtype(cfg.dtype).itemsize
    if _reads_own_pages(t, cfg.head_dim):
        from ray_tpu.ops import paged_attention as _pa
        return _pa.keys_copied(last, page_size, nblk, 2 * key) \
            * cfg.n_layers
    cols = page_size * paged_span_blocks(rows * page_size * key,
                                         page_size, nblk)
    spans = -(-(int(last.max()) + 1) // cols)
    return rows * cfg.n_layers * spans * cols


def paged_chunk_step(params: Dict, tokens, pos, cache: Dict,
                     block_tables, cfg, pad_lo=None, **row
                     ) -> Tuple[Any, Dict]:
    """Decode a chunk of t tokens [B, t] through a PAGED cache, by the
    step of `cfg`'s body (`row` carries what only a body whose
    `chunk_takes_row` takes: the decode row a single-row chunk belongs
    to and how many of its tokens are real).

    `block_tables` [B, nblk] maps each row's virtual cache columns to
    pages of the pool: virtual column c lives at
    (block_tables[b, c // page], c % page).  `pos` is a scalar (one
    shared start column — single-row prefill) or a [B] vector (each row
    chunked at its own depth — the decode tick at t = 1, the fused
    speculative verify).  Row b's chunk K/V is scattered at columns
    pos[b]..pos[b]+t-1 through its table; query i of row b then sees
    columns pad_lo[b]..pos[b]+i, which hold bit-identical values to a
    contiguous cache, so paging is invisible to results.

    A body whose `block` B is not 1 keeps another mask: query i sees
    every column of its own block of B and of the blocks before it.  Its
    single-row chunk (scalar `pos`, whole pages, B dividing a page) shows
    query i columns 0..min(((pos+i)//B + 1) B, pos+valid) - 1; its
    per-row call is a BLOCK STEP, t = B columns a row at a `pos[b]` that
    is a multiple of B: the row's block is written at pos[b]..pos[b]+B-1,
    over whatever an earlier step wrote there, and every one of its B
    queries sees columns 0..pos[b]+B-1.  The logits of column i are of
    the token AT column i.  Such a body takes no other `t`.

    Callers must keep pos+t within nblk*page (writes past the table
    would clip into the last block).  Returns (logits [B, t, V] fp32,
    updated cache)."""
    return paged_body(cfg).paged_chunk_step(params, tokens, pos, cache,
                                            block_tables, cfg,
                                            pad_lo=pad_lo, **row)


def _dense_chunk_step(params: Dict, tokens, pos, cache: Dict,
                      block_tables, cfg, pad_lo=None) -> Tuple[Any, Dict]:
    """The dense body's step (GPT, Llama), under paged_chunk_step's.

    Attention reads the width the rows HOLD, not the table's.  A TICK on
    a TPU (one token a row at per-row positions, no left padding:
    `_reads_own_pages`) reads each row's OWN pages to its own position:
    a layer's attention is one `ops/paged_attention.py` kernel, handed
    the pools as they lie ([L, P, page, Hkv, Dh] is its "heads in rows"
    form) and the layer's index, queries and pages in the pool's type,
    softmax and accumulator float32; a row at position 0 is idle and
    reads nothing.  Every other call (the single-row chunk, the
    speculative verify, left-padded rows, a backend that is no TPU)
    walks SPANS of paged_span_blocks consecutive table entries (the
    reference the kernel is held equal to: tests/test_decode.py) up to
    the deepest row's last column, `ceil((max(pos) + t) / span columns)` of
    them — a trip count read from `pos` inside the one compiled
    program, so a call's bytes follow the tokens cached and no table
    width compiles a program of its own.  Each span's pages are
    gathered straight from the pool, scored in float32, masked and
    merged into a running maximum, sum and accumulator (the softmax of
    the whole row, its sums taken span by span).  The table's tail may
    be shorter than a span: the slice is then clamped back over columns
    the span before it covered, and those are masked by their index.

    The pool rides the layer scan as part of its CARRY — (x, k, v) with
    k/v the whole [L, P, page, Hkv, Dh] tensors and the layer index l
    scanned from arange(L) — and each layer scatters its chunk at
    [l, w_pages, w_offs] before the span loop (or the kernel), which
    only reads it, at [l, the span's pages]; no per-layer slice
    cache["k"][l] is formed.
    The compiler then updates the donated buffer in place and a call
    touches only the pages it writes and reads.  Held as the scan's
    xs/ys instead, every layer's pool is sliced out, updated and
    stacked into a second pool, which is copied whole after the loop:
    six pool-sized moves a call and ~4 GiB of temporaries at a 7B
    model's widths (tests/test_tpu_compile.py holds the line)."""
    B, t = tokens.shape
    psz = cache["k"].shape[2]
    nblk = block_tables.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    offs = jnp.arange(t)
    cols = jnp.broadcast_to(jnp.reshape(pos, (-1, 1)) + offs[None, :],
                            (B, t))                    # global columns
    ragged = pos.ndim == 1 and pad_lo is None \
        and _reads_own_pages(t, cache["k"].shape[4])
    if pad_lo is None:
        pad_lo = jnp.zeros((B,), jnp.int32)
    positions = cols - pad_lo[:, None]
    x = _embed(params, tokens, positions, cfg)
    w_pages = jnp.take_along_axis(block_tables, cols // psz, axis=1)
    w_offs = cols % psz

    Hkv, Dh = cache["k"].shape[3:]
    span = paged_span_blocks(
        B * psz * Hkv * Dh * cache["k"].dtype.itemsize, psz, nblk)
    span_cols = span * psz
    n_spans = (jnp.max(pos) + t + span_cols - 1) // span_cols
    span_offs = jnp.arange(span_cols)

    def own_pages(q, ck_all, cv_all, l):
        """The tick's attention of layer l: each row's own pages.  (The
        kernel's module is imported where a TPU's tick is traced, as
        models/gpt.py imports its flash kernel: Pallas is a second of
        imports that no other process of a dense model needs.)"""
        from ray_tpu.ops import paged_attention as _pa
        return _pa.paged_attention(
            q[:, 0].astype(ck_all.dtype), ck_all, cv_all, l, block_tables,
            pos, n_kv_heads=Hkv)[:, None]

    def spans(q, ck_all, cv_all, l):
        """Layer l's attention span by span, to the deepest row."""
        rep = q.shape[2] // Hkv
        qg = q.reshape(B, t, Hkv, rep, Dh).astype(jnp.float32)

        def attend(i, part):
            top, total, acc = part
            first = jnp.minimum(i * span, nblk - span)   # as the slice clamps
            pages = lax.dynamic_slice(block_tables, (0, first), (B, span))
            ck = ck_all[l, pages].reshape(B, span_cols, Hkv, Dh)
            cv = cv_all[l, pages].reshape(B, span_cols, Hkv, Dh)
            kcols = first * psz + span_offs
            lo = jnp.maximum(pad_lo, i * span_cols)
            mask = (kcols[None, None, :] <= cols[:, :, None]) \
                & (kcols[None, None, :] >= lo[:, None, None])
            scores = jnp.einsum("bqgrk,bsgk->bgrqs", qg,
                                ck.astype(jnp.float32)) \
                * cfg.head_dim ** -0.5
            scores = jnp.where(mask[:, None, None], scores, -jnp.inf)
            new = jnp.maximum(top, scores.max(-1))
            # a row none of whose columns has come yet (all of a span
            # under its pad_lo) keeps -inf: subtract 0 there, not -inf
            ref = jnp.where(jnp.isfinite(new), new, 0.0)
            e = jnp.exp(scores - ref[..., None])
            keep = jnp.exp(top - ref)
            out = jnp.einsum("bgrqs,bsgk->bgrqk", e.astype(cv.dtype), cv,
                             preferred_element_type=jnp.float32)
            return (new, total * keep + e.sum(-1),
                    acc * keep[..., None] + out)

        stat = jnp.full((B, Hkv, rep, t), -jnp.inf, jnp.float32)
        _, total, acc = lax.fori_loop(
            0, n_spans, attend,
            (stat, jnp.zeros_like(stat),
             jnp.zeros((B, Hkv, rep, t, Dh), jnp.float32)))
        out = (acc / total[..., None]).astype(cv_all.dtype)
        return jnp.moveaxis(out, 3, 1).reshape(B, t, q.shape[2], Dh)

    def layer(carry, inputs):
        x, ck_all, cv_all = carry                # [L, P, psz, Hkv, Dh]
        lp, l = inputs
        h = _rmsnorm(x, lp["ln1"])
        q, k, v = _qkv(lp, h, positions, cfg)
        ck_all = ck_all.at[l, w_pages, w_offs].set(k.astype(ck_all.dtype))
        cv_all = cv_all.at[l, w_pages, w_offs].set(v.astype(cv_all.dtype))
        with jax.named_scope("dense_attn"):
            out = own_pages(q, ck_all, cv_all, l) if ragged \
                else spans(q, ck_all, cv_all, l)
        x = x + _attn_out(lp, out, cfg)
        x = _ffn(lp, x, cfg)
        return (x, ck_all, cv_all), None

    (x, ck, cv), _ = lax.scan(
        layer, (x, cache["k"], cache["v"]),
        (params["blocks"], jnp.arange(cache["k"].shape[0])))
    return _final_logits(params, x, cfg), {"k": ck, "v": cv}


DENSE_BODY = PagedBody(
    init_paged_cache=_dense_paged_cache, paged_chunk_step=_dense_chunk_step,
    check_paging=_dense_check_paging, attn_keys=_dense_attn_keys,
    framed=True, chunk_takes_row=False,
    attn_keys_gathered=_dense_attn_keys_gathered)


def _cached_attention(q, ck, cv, pos, pad_lo, cfg):
    """q [B,1,H,Dh] against the cache's first pos+1 positions (static
    shape: positions > pos are masked, not sliced; columns < pad_lo[b]
    are left-padding and masked too).  `pos` is a scalar (whole batch at
    one column — the lockstep generate() path) or a [B] vector (each
    row at its own depth — the continuous-batching engine).  GQA stays
    at Hkv width: q is folded to [B,1,Hkv,rep,Dh] and contracted
    against the Hkv-sized cache — no repeated cache copy per step."""
    B, S, Hkv, Dh = ck.shape
    rep = q.shape[2] // Hkv
    qg = q.reshape(B, 1, Hkv, rep, Dh)
    scale = cfg.head_dim ** -0.5
    scores = jnp.einsum("bqgrk,bsgk->bgrqs", qg.astype(jnp.float32),
                        ck.astype(jnp.float32)) * scale
    cols = jnp.arange(S)
    pos_col = jnp.reshape(jnp.asarray(pos), (-1, 1))  # [1,1] or [B,1]
    mask = (cols[None, :] <= pos_col) \
        & (cols[None, :] >= pad_lo[:, None])
    scores = jnp.where(mask[:, None, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgrqs,bsgk->bqgrk", probs.astype(cv.dtype), cv)
    return out.reshape(B, 1, Hkv * rep, Dh)


# ---------------------------------------------------------------------------
# Prefill + single-step decode


def prefill(params: Dict, tokens, cfg, cache: Dict, prompt_lens=None
            ) -> Tuple[Any, Dict]:
    """Run the prompt [B, T] through the model, filling cache[:, :, :T].

    With `prompt_lens` [B], rows are treated as LEFT-padded to width T:
    row b's real tokens occupy columns T-len..T-1, get logical
    positions 0..len-1, and its padding columns are masked out of every
    attention (they contribute nothing to any real token).

    Returns (logits [B, T, V] fp32, cache)."""
    B, T = tokens.shape
    cols = jnp.arange(T)
    if prompt_lens is None:
        pad_lo = jnp.zeros((B,), jnp.int32)       # first real column
        positions = jnp.broadcast_to(cols, (B, T))
    else:
        pad_lo = (T - jnp.asarray(prompt_lens, jnp.int32))
        positions = jnp.maximum(cols[None, :] - pad_lo[:, None], 0)
    x = _embed(params, tokens, positions, cfg)
    # causal AND not-padding: [B, q, k].  Pad queries additionally
    # attend to THEMSELVES: a query with zero valid keys softmaxes an
    # all--inf row into NaNs, and those NaNs reach real columns through
    # 0-weight * NaN-value products in the next layer's value einsum —
    # self-attention keeps pad lanes finite (their outputs are garbage
    # but masked out of every real token's view).
    mask = (cols[None, None, :] <= cols[None, :, None]) \
        & ((cols[None, None, :] >= pad_lo[:, None, None])
           | (cols[None, None, :] == cols[None, :, None]))

    def layer(x, inputs):
        lp, ck_l, cv_l = inputs
        h = _rmsnorm(x, lp["ln1"])
        q, k, v = _qkv(lp, h, positions, cfg)
        ck_l = lax.dynamic_update_slice(
            ck_l, k.astype(ck_l.dtype), (0, 0, 0, 0))
        cv_l = lax.dynamic_update_slice(
            cv_l, v.astype(cv_l.dtype), (0, 0, 0, 0))
        rep = q.shape[2] // k.shape[2]
        if rep > 1:
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        scores = jnp.einsum("bqhk,bshk->bhqs", q.astype(jnp.float32),
                            k.astype(jnp.float32)) \
            * cfg.head_dim ** -0.5
        scores = jnp.where(mask[:, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhqs,bshk->bqhk", probs.astype(v.dtype), v)
        x = x + _attn_out(lp, out, cfg)
        x = _ffn(lp, x, cfg)
        return x, (ck_l, cv_l)

    x, (ck, cv) = lax.scan(layer, x,
                           (params["blocks"], cache["k"], cache["v"]))
    return _final_logits(params, x, cfg), {"k": ck, "v": cv}


def decode_step(params: Dict, token, pos, cache: Dict, cfg,
                pad_lo=None) -> Tuple[Any, Dict]:
    """One token [B] at cache column pos -> (logits [B, V], updated
    cache).  `pos` is a scalar int (every row writes the same column —
    whole-batch generate()) or a [B] int vector (each row writes its OWN
    column — continuous batching, where slots are mid-generation at
    different depths; writes become a per-row scatter).  pad_lo [B]
    marks each row's first real cache column (0 without left-padding).
    Jit once per shape; every step reuses the compilation."""
    B = token.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    per_row = pos.ndim == 1
    if pad_lo is None:
        pad_lo = jnp.zeros((B,), jnp.int32)
    positions = (pos - pad_lo)[:, None]  # logical position per row
    rows = jnp.arange(B)

    x = _embed(params, token[:, None], positions, cfg)

    def layer(x, inputs):
        lp, ck_l, cv_l = inputs
        h = _rmsnorm(x, lp["ln1"])
        q, k, v = _qkv(lp, h, positions, cfg)
        if per_row:
            ck_l = ck_l.at[rows, pos].set(k[:, 0].astype(ck_l.dtype))
            cv_l = cv_l.at[rows, pos].set(v[:, 0].astype(cv_l.dtype))
        else:
            ck_l = lax.dynamic_update_slice(
                ck_l, k.astype(ck_l.dtype), (0, pos, 0, 0))
            cv_l = lax.dynamic_update_slice(
                cv_l, v.astype(cv_l.dtype), (0, pos, 0, 0))
        out = _cached_attention(q, ck_l, cv_l, pos, pad_lo, cfg)
        x = x + _attn_out(lp, out, cfg)
        x = _ffn(lp, x, cfg)
        return x, (ck_l, cv_l)

    x, (ck, cv) = lax.scan(layer, x,
                           (params["blocks"], cache["k"], cache["v"]))
    return _final_logits(params, x, cfg)[:, 0], {"k": ck, "v": cv}


def chunk_step(params: Dict, tokens, pos, cache: Dict, cfg,
               pad_lo=None) -> Tuple[Any, Dict]:
    """Decode a CHUNK of t tokens [B, t] starting at cache column pos
    (scalar) in one forward: used by speculative verification, where
    the draft's t tokens are scored together instead of one dispatch
    per token.  Returns (logits [B, t, V], cache with the chunk's K/V
    written at pos..pos+t-1)."""
    B, t = tokens.shape
    if pad_lo is None:
        pad_lo = jnp.zeros((B,), jnp.int32)
    offs = jnp.arange(t)
    positions = (pos + offs)[None, :] - pad_lo[:, None]
    x = _embed(params, tokens, positions, cfg)

    def layer(x, inputs):
        lp, ck_l, cv_l = inputs
        h = _rmsnorm(x, lp["ln1"])
        q, k, v = _qkv(lp, h, positions, cfg)
        ck_l = lax.dynamic_update_slice(
            ck_l, k.astype(ck_l.dtype), (0, pos, 0, 0))
        cv_l = lax.dynamic_update_slice(
            cv_l, v.astype(cv_l.dtype), (0, pos, 0, 0))
        # q col i (global pos+i) sees cache cols in [pad_lo, pos+i].
        S = ck_l.shape[1]
        Hkv = ck_l.shape[2]
        rep = q.shape[2] // Hkv
        qg = q.reshape(B, t, Hkv, rep, -1)
        scores = jnp.einsum("bqgrk,bsgk->bgrqs",
                            qg.astype(jnp.float32),
                            ck_l.astype(jnp.float32)) \
            * cfg.head_dim ** -0.5
        cols = jnp.arange(S)
        mask = (cols[None, None, :] <= (pos + offs)[None, :, None]) \
            & (cols[None, None, :] >= pad_lo[:, None, None])
        scores = jnp.where(mask[:, None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bgrqs,bsgk->bqgrk", probs.astype(cv_l.dtype),
                         cv_l)
        out = out.reshape(B, t, q.shape[2], -1)
        x = x + _attn_out(lp, out, cfg)
        x = _ffn(lp, x, cfg)
        return x, (ck_l, cv_l)

    x, (ck, cv) = lax.scan(layer, x,
                           (params["blocks"], cache["k"], cache["v"]))
    return _final_logits(params, x, cfg), {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# Generation


def _sample(logits, key, temperature: float, top_k: int):
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k > 0:
        kth = jnp.sort(logits, axis=-1)[..., -top_k][..., None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    return jax.random.categorical(key, logits).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("cfg", "max_new_tokens",
                                             "temperature", "top_k"))
def _generate_jit(params, prompt, prompt_lens, cfg, max_new_tokens,
                  temperature, top_k, key):
    B, T = prompt.shape
    S = T + max_new_tokens
    cache = init_cache(cfg, B, max_seq=S)
    pad_lo = T - prompt_lens
    logits, cache = prefill(params, prompt, cfg, cache,
                            prompt_lens=prompt_lens)
    key, sub = jax.random.split(key)
    first = _sample(logits[:, -1], sub, temperature, top_k)

    def step(carry, _):
        token, pos, cache, key = carry
        logits, cache = decode_step(params, token, pos, cache, cfg,
                                    pad_lo=pad_lo)
        key, sub = jax.random.split(key)
        nxt = _sample(logits, sub, temperature, top_k)
        return (nxt, pos + 1, cache, key), token

    (last, _, _, _), toks = lax.scan(
        step, (first, jnp.int32(T), cache, key), None,
        length=max_new_tokens - 1)
    toks = jnp.moveaxis(toks, 0, 1)  # [B, max_new-1]
    return jnp.concatenate([toks, last[:, None]], axis=1)


@functools.partial(jax.jit, static_argnames=("cfg", "max_new_tokens",
                                             "ngram", "k"))
def _generate_speculative_jit(params, prompt, prompt_lens, cfg,
                              max_new_tokens, ngram, k):
    """Greedy prompt-lookup speculative decoding (the draft model is
    the context itself: the k tokens that followed the most recent
    earlier occurrence of the current n-gram).  One chunk_step scores
    all k drafts + the bonus token per iteration; the acceptance rule
    (keep the longest prefix where draft == argmax) makes the output
    IDENTICAL to plain greedy decode — speculation changes dispatch
    count, never results.  Stale cache/buffer entries past the accept
    point sit at columns > pos and are invisible to the masked
    attention until overwritten."""
    B, T = prompt.shape
    S = T + max_new_tokens + k + 1  # slack for the last chunk's writes
    cache = init_cache(cfg, B, max_seq=S)
    pad_lo = T - prompt_lens
    logits, cache = prefill(params, prompt, cfg, cache,
                            prompt_lens=prompt_lens)
    first = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    buf = jnp.concatenate(
        [prompt.astype(jnp.int32),
         jnp.zeros((B, S - T), jnp.int32)], axis=1)
    buf = lax.dynamic_update_slice(buf, first[:, None], (0, T))
    end = T + max_new_tokens

    def lookup(buf, pos):
        """Per row: tokens following the latest earlier occurrence of
        buf[pos-ngram+1 .. pos] (the n-gram ENDING at the pending
        token); zeros when no match."""
        key = lax.dynamic_slice(
            buf, (0, pos - (ngram - 1)), (B, ngram))
        # windows starting at j cover buf[j .. j+ngram-1]
        idx = jnp.arange(S - ngram + 1)[:, None] + jnp.arange(ngram)
        wins = buf[:, idx]                       # [B, S-n+1, n]
        hit = jnp.all(wins == key[:, None, :], axis=-1)
        starts = jnp.arange(S - ngram + 1)
        # candidate must END before pos and leave room to read k tokens
        ok = (starts + ngram - 1 < pos) & hit
        j = jnp.max(jnp.where(ok, starts, -1), axis=1)  # latest match
        has = j >= 0
        draft_start = jnp.where(has, j + ngram, 0)
        gather = draft_start[:, None] + jnp.arange(k)[None]
        draft = jnp.take_along_axis(buf, gather, axis=1)
        return jnp.where(has[:, None], draft, 0)

    def cond(carry):
        _, pos, _, _, _ = carry
        return pos < end

    def body(carry):
        token, pos, cache, buf, iters = carry
        draft = lookup(buf, pos)                       # [B, k]
        chunk = jnp.concatenate([token[:, None], draft], axis=1)
        logits, cache = chunk_step(params, chunk, pos, cache, cfg,
                                   pad_lo=pad_lo)
        preds = jnp.argmax(logits, -1).astype(jnp.int32)  # [B, k+1]
        # accepted[i] = all drafts before i matched the model
        match = preds[:, :-1] == draft                 # [B, k]
        acc = jnp.cumprod(match.astype(jnp.int32), axis=1)
        m = jnp.sum(acc, axis=1)                       # 0..k per row
        # lockstep batch: advance by the batch MINIMUM (every row's
        # cache write head must stay identical for the shared pos)
        m_min = jnp.minimum(jnp.min(m), end - 1 - pos)
        # outputs: accepted drafts then the bonus prediction at m_min
        out_chunk = jnp.concatenate([draft, jnp.zeros((B, 1),
                                                      jnp.int32)], 1)
        bonus = jnp.take_along_axis(preds, m_min[None].repeat(B)[:,
                                                                 None],
                                    axis=1)[:, 0]
        out_chunk = jnp.where(
            jnp.arange(k + 1)[None, :] == m_min, bonus[:, None],
            out_chunk)
        keep = jnp.arange(k + 1)[None, :] <= m_min
        cur = lax.dynamic_slice(buf, (0, pos + 1), (B, k + 1))
        buf = lax.dynamic_update_slice(
            buf, jnp.where(keep, out_chunk, cur), (0, pos + 1))
        token = bonus
        return token, pos + m_min + 1, cache, buf, iters + 1

    token0 = first
    carry = (token0, jnp.int32(T), cache, buf, jnp.int32(0))
    _, _, _, buf, iters = lax.while_loop(cond, body, carry)
    return lax.dynamic_slice(buf, (0, T), (B, max_new_tokens)), iters


def generate(params: Dict, prompt, cfg, *, max_new_tokens: int,
             temperature: float = 0.0, top_k: int = 0,
             key=None, eos_token: Optional[int] = None,
             prompt_lens=None, speculate_ngram: int = 0,
             speculate_k: int = 0, return_stats: bool = False):
    """prompt [B, T] -> generated tokens [B, max_new_tokens].

    temperature 0 = greedy; top_k > 0 restricts sampling.  One jit
    compilation per (shape, cfg, knobs); the whole loop runs on device
    as a single dispatch.  Mixed-length batches: LEFT-pad each row to a
    common width and pass `prompt_lens` [B] — pad columns are masked
    out of attention and logical positions start at each row's first
    real token, so results match per-row unbatched generation.

    Return type depends on eos_token: WITHOUT it, a [B, max_new_tokens]
    array; WITH it, a ragged LIST of per-row 1-D arrays, each truncated
    before its first EOS (truncation is host-side so the device loop
    stays static-shape)."""
    if getattr(cfg, "n_experts", 0):
        raise NotImplementedError("decode supports dense models (MoE "
                                  "routing caches are not implemented)")
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, "
                         f"got {max_new_tokens}")
    B, T = prompt.shape
    S = T + max_new_tokens + (speculate_k + 1 if speculate_k else 0)
    if not _is_llama(cfg) and S > cfg.max_seq:
        raise ValueError(f"prompt + max_new_tokens (+ speculative "
                         f"slack) = {S} exceeds max_seq={cfg.max_seq} "
                         f"(learned positions)")
    key = key if key is not None else jax.random.PRNGKey(0)
    if prompt_lens is None:
        prompt_lens = jnp.full((B,), T, jnp.int32)
    else:
        prompt_lens = jnp.asarray(prompt_lens, jnp.int32)
    stats = None
    if speculate_k > 0:
        # Prompt-lookup speculation: greedy-only (sampled acceptance
        # needs rejection sampling; out of scope) — the output is
        # bit-identical to plain greedy decode, only faster.
        if temperature > 0.0:
            raise ValueError("speculative decoding is greedy-only "
                             "(temperature must be 0)")
        if speculate_ngram < 1:
            raise ValueError("speculate_ngram must be >= 1 when "
                             "speculate_k is set")
        if T < speculate_ngram:
            raise ValueError(f"prompt length {T} shorter than "
                             f"speculate_ngram={speculate_ngram}")
        out, iters = _generate_speculative_jit(
            params, jnp.asarray(prompt, jnp.int32), prompt_lens, cfg,
            max_new_tokens, int(speculate_ngram), int(speculate_k))
        stats = {"verify_steps": int(iters),
                 "tokens_per_step": max_new_tokens / max(1, int(iters))}
    else:
        out = _generate_jit(params, jnp.asarray(prompt, jnp.int32),
                            prompt_lens, cfg, max_new_tokens,
                            float(temperature), int(top_k), key)
    if eos_token is not None:
        arr = np.asarray(out)
        # one vectorized argmax over the hit mask, not an O(B) host
        # loop of np.where: rows without an EOS keep their full width.
        hit = arr == eos_token
        cut = np.where(hit.any(axis=1), hit.argmax(axis=1), arr.shape[1])
        out = [row[:n] for row, n in zip(arr, cut)]
    return (out, stats) if return_stats else out
