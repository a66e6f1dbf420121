"""Paged KV cache, radix prefix reuse, and in-engine speculation.

Three layers under test:

  * paging.py bookkeeping — refcounted BlockAllocator + RadixPrefixCache
    (the load-bearing invariant: evicting one sharer of a prefix page
    must never free a page another request still gathers through);
  * decode.paged_chunk_step — block-table attention must match the
    contiguous cache kernels for any page permutation;
  * the engine — THE acceptance property is parity: random arrival
    schedules x {prefix full hit, partial hit, miss} x {speculation
    on/off} must all stream tokens bit-identical to per-prompt greedy
    decode.generate(), plus free-page-bounded admission and the
    structured queue_full / kv_exhausted backpressure split.
"""

import asyncio
import random
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import decode, gpt, llama
from ray_tpu.serve.llm import (BlockAllocator, EngineOverloadedError,
                               GenerationEngine, RadixPrefixCache)
from ray_tpu.serve.llm import engine as engine_mod

GPT_CFG = gpt.GPTConfig(vocab_size=97, d_model=32, n_heads=4,
                        n_layers=2, d_ff=64, max_seq=64,
                        dtype=jnp.float32, remat=False, use_flash=False)
LLAMA_CFG = llama.LlamaConfig(vocab_size=97, d_model=32, n_heads=4,
                              n_kv_heads=2, n_layers=2, d_ff=48,
                              max_seq=64, dtype=jnp.float32,
                              remat=False, use_flash=False)


def _params(cfg):
    mod = llama if isinstance(cfg, llama.LlamaConfig) else gpt
    return mod.init_params(cfg, jax.random.PRNGKey(0))


GPT_PARAMS = _params(GPT_CFG)

# One shared shape vocabulary so jit compilations are reused across
# tests: 3 rows, page 4, max_seq 48, chunk-5 prefill.
PAGED_KW = dict(num_slots=3, max_seq=48, prefill_chunk=5, page_size=4,
                kv_pages=40)


def _prompt(seed, n, cfg=GPT_CFG):
    return [int(t) for t in np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (n,), 1, cfg.vocab_size))]


def _oracle(params, cfg, prompt, max_new, eos_token=None):
    out = decode.generate(params, jnp.asarray([prompt]), cfg,
                          max_new_tokens=max_new, eos_token=eos_token)
    return np.asarray(out[0])


# ---------------------------------------------------------------------------
# BlockAllocator


def test_block_allocator_refcounted_alloc_free():
    a = BlockAllocator(4, first_page=1)
    assert a.free_pages == 4
    pages = a.alloc(3)
    assert sorted(pages) == [1, 2, 3] and a.free_pages == 1
    # all-or-nothing: a too-big request leaves the free list untouched
    assert a.alloc(2) is None
    assert a.free_pages == 1
    # shared page: the second holder keeps it alive
    a.incref(pages[0])
    assert not a.decref(pages[0])          # one ref left
    assert a.refcount(pages[0]) == 1
    assert a.free_pages == 1
    assert a.decref(pages[0])              # last ref frees
    assert a.free_pages == 2
    with pytest.raises(ValueError):
        a.decref(pages[0])                 # double free is loud
    for p in pages[1:]:
        a.decref(p)
    assert a.free_pages == 4


def test_radix_cache_match_insert_evict():
    a = BlockAllocator(8, first_page=1)
    rc = RadixPrefixCache(2, a)
    toks = [5, 6, 7, 8, 9, 10]
    pages = a.alloc(3)
    rc.insert(toks, pages)                 # tree now holds 3 refs
    assert rc.nodes == 3
    # full-page match only; max_tokens caps the walk
    got, n = rc.match(toks)
    assert got == pages and n == 6
    got, n = rc.match(toks, max_tokens=5)  # cap at 5 -> 2 full pages
    assert got == pages[:2] and n == 4
    got, n = rc.match([5, 6, 7, 99])       # diverges in page 2
    assert got == pages[:1] and n == 2
    assert rc.match([1, 2]) == ([], 0)
    # releasing the requester's own refs leaves pages tree-held
    for p in pages:
        a.decref(p)
    assert a.free_pages == 5
    # evicting one sharer's node must not free a page a live holder
    # still reads: hold page[2] as a "request", then evict everything
    a.incref(pages[2])
    rc.evict(8)                            # wants all 8 free
    assert rc.nodes == 0
    assert a.free_pages == 7               # pages[2] survives its node
    assert a.refcount(pages[2]) == 1
    a.decref(pages[2])
    assert a.free_pages == 8


def test_radix_releasable_counts_tree_only_pages():
    """releasable() is the engine's evict-worthiness pre-check: pages a
    full wipe could actually free (tree-only holders).  A reservation
    that even a full wipe cannot cover must not destroy the cache."""
    a = BlockAllocator(6, first_page=1)
    rc = RadixPrefixCache(2, a)
    pages = a.alloc(3)
    rc.insert([1, 2, 3, 4, 5, 6], pages)
    # requester still holds all 3 -> nothing is releasable yet
    assert rc.releasable() == 0
    a.decref(pages[0])
    a.decref(pages[1])
    assert rc.releasable() == 2              # two tree-only pages now
    # free=3, releasable=2: a 6-page ask is unsatisfiable — the engine
    # skips evict() in that case; a 5-page ask is coverable
    assert a.free_pages + rc.releasable() < 6
    assert a.free_pages + rc.releasable() >= 5
    rc.evict(5)
    assert a.free_pages == 5
    # the shared leaf's NODE went (it blocked the interior pages) but
    # its page survives on the requester's ref
    assert rc.nodes == 0
    assert a.refcount(pages[2]) == 1
    a.decref(pages[2])
    assert a.free_pages == 6


def test_radix_cache_lru_eviction_order():
    a = BlockAllocator(4, first_page=1)
    rc = RadixPrefixCache(2, a)
    p1 = a.alloc(1)
    p2 = a.alloc(1)
    rc.insert([1, 2], p1)
    rc.insert([3, 4], p2)
    for p in p1 + p2:
        a.decref(p)
    rc.match([1, 2])                       # touch branch 1 -> MRU
    rc.evict(3)                            # need one page back
    assert rc.nodes == 1
    assert rc.match([1, 2])[1] == 2        # MRU branch survived
    assert rc.match([3, 4])[1] == 0        # LRU branch evicted


def test_radix_insert_dedups_existing_chunks():
    a = BlockAllocator(8, first_page=1)
    rc = RadixPrefixCache(2, a)
    first = a.alloc(2)
    rc.insert([1, 2, 3, 4], first)
    dup = a.alloc(2)
    added = rc.insert([1, 2, 3, 4, 5, 6], dup + a.alloc(1))
    assert added == 1                      # only the NEW third chunk
    got, n = rc.match([1, 2, 3, 4, 5, 6])
    assert n == 6
    assert got[:2] == first                # original pages kept


# ---------------------------------------------------------------------------
# Paged decode kernels


@pytest.mark.parametrize(
    "cfg", [GPT_CFG,
            pytest.param(LLAMA_CFG, marks=pytest.mark.slow)],
    ids=["gpt", "llama"])
def test_paged_chunk_step_matches_contiguous(cfg):
    """Block-table attention with SCRAMBLED page order must produce the
    same logits as the contiguous-cache kernels, chunked prefill and
    per-row-depth decode alike."""
    params = _params(cfg)
    psz, nblk = 4, 6                       # virtual width 24
    lens = [5, 9]
    seqs = [jax.random.randint(jax.random.PRNGKey(40 + i), (1, n), 1,
                               cfg.vocab_size) for i, n in enumerate(lens)]
    # contiguous oracle: per-request caches
    solo = []
    for i, (seq, n) in enumerate(zip(seqs, lens)):
        c = decode.init_cache(cfg, 1, max_seq=nblk * psz)
        _, c = decode.prefill(params, seq, cfg, c)
        tok = jnp.asarray([7 + i], jnp.int32)
        lg, c = decode.decode_step(params, tok, jnp.int32(n), c, cfg)
        solo.append((lg, c))
    # paged: one pool, rows own interleaved non-contiguous pages
    # (page 0 deliberately unused, mirroring the engine's trash page)
    pool = decode.init_paged_cache(cfg, 2 * nblk + 1, psz)
    tables = np.asarray([[2, 4, 6, 8, 10, 12],
                         [11, 3, 9, 1, 7, 5]], np.int32)
    for i, (seq, n) in enumerate(zip(seqs, lens)):
        lg, pool = decode.paged_chunk_step(
            params, seq, jnp.int32(0), pool,
            jnp.asarray(tables[i:i + 1]), cfg)
        np.testing.assert_allclose(
            np.asarray(lg[0, n - 1]),
            np.asarray(decode.prefill(
                params, seq, cfg,
                decode.init_cache(cfg, 1, max_seq=nblk * psz))[0][0, n - 1]),
            rtol=1e-6, atol=1e-7)
    toks = jnp.asarray([7, 8], jnp.int32)
    pos = jnp.asarray(lens, jnp.int32)
    logits, pool = decode.paged_chunk_step(params, toks[:, None], pos, pool,
                                           jnp.asarray(tables), cfg)
    logits = logits[:, 0]
    for i in range(2):
        np.testing.assert_allclose(np.asarray(logits[i]),
                                   np.asarray(solo[i][0][0]),
                                   rtol=1e-6, atol=1e-7)
        # gathered pages hold exactly the contiguous cache's content
        pk = np.asarray(pool["k"])[:, tables[i]].reshape(
            cfg.n_layers, nblk * psz, -1)
        sk = np.asarray(solo[i][1]["k"])[:, 0].reshape(
            cfg.n_layers, nblk * psz, -1)
        cols = lens[i] + 1                 # written columns so far
        np.testing.assert_allclose(pk[:, :cols], sk[:, :cols],
                                   rtol=1e-6, atol=1e-7)


def test_paged_writes_touch_only_own_pages():
    """A row's scatter writes must land only in ITS block table's pages
    — the page-pool twin of the old touch-only-their-row test."""
    cfg, params = GPT_CFG, GPT_PARAMS
    psz = 4
    pool = decode.init_paged_cache(cfg, 7, psz)
    t1 = np.asarray([[1, 2, 3]], np.int32)
    t2 = np.asarray([[4, 5, 6]], np.int32)
    seq = jax.random.randint(jax.random.PRNGKey(50), (1, 8), 1,
                             cfg.vocab_size)
    _, pool = decode.paged_chunk_step(params, seq, jnp.int32(0), pool,
                                      jnp.asarray(t1), cfg)
    before = np.asarray(pool["k"])
    assert np.abs(before[:, [1, 2, 3]]).max() > 0
    assert np.abs(before[:, [4, 5, 6]]).max() == 0
    _, pool = decode.paged_chunk_step(params, seq, jnp.int32(0), pool,
                                      jnp.asarray(t2), cfg)
    after = np.asarray(pool["k"])
    np.testing.assert_array_equal(after[:, [1, 2, 3]],
                                  before[:, [1, 2, 3]])
    assert np.abs(after[:, [4, 5, 6]]).max() > 0


def _paged_chunk_step_sliced(params, tokens, pos, cache, block_tables, cfg):
    """FROZEN copy of paged_chunk_step as it stood before PR 26: each
    layer's pool is a scanned input, written and gathered as a slice,
    and stacked back as a scanned output.  The oracle whose bytes the
    carry-held pool must reproduce; used by nothing else."""
    from jax import lax
    B, t = tokens.shape
    psz = cache["k"].shape[2]
    S = block_tables.shape[1] * psz
    pos = jnp.asarray(pos, jnp.int32)
    cols = jnp.broadcast_to(
        jnp.reshape(pos, (-1, 1)) + jnp.arange(t)[None, :], (B, t))
    pad_lo = jnp.zeros((B,), jnp.int32)
    positions = cols - pad_lo[:, None]
    x = decode._embed(params, tokens, positions, cfg)
    w_pages = jnp.take_along_axis(block_tables, cols // psz, axis=1)
    w_offs = cols % psz
    kcols = jnp.arange(S)
    mask = (kcols[None, None, :] <= cols[:, :, None]) \
        & (kcols[None, None, :] >= pad_lo[:, None, None])

    def layer(x, inputs):
        lp, ck_l, cv_l = inputs                  # [P, psz, Hkv, Dh]
        h = decode._rmsnorm(x, lp["ln1"])
        q, k, v = decode._qkv(lp, h, positions, cfg)
        ck_l = ck_l.at[w_pages, w_offs].set(k.astype(ck_l.dtype))
        cv_l = cv_l.at[w_pages, w_offs].set(v.astype(cv_l.dtype))
        Hkv, Dh = ck_l.shape[2], ck_l.shape[3]
        ck = ck_l[block_tables].reshape(B, S, Hkv, Dh)
        cv = cv_l[block_tables].reshape(B, S, Hkv, Dh)
        rep = q.shape[2] // Hkv
        qg = q.reshape(B, t, Hkv, rep, Dh)
        scores = jnp.einsum("bqgrk,bsgk->bgrqs", qg.astype(jnp.float32),
                            ck.astype(jnp.float32)) \
            * cfg.head_dim ** -0.5
        scores = jnp.where(mask[:, None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bgrqs,bsgk->bqgrk", probs.astype(cv.dtype), cv)
        out = out.reshape(B, t, q.shape[2], Dh)
        x = x + decode._attn_out(lp, out, cfg)
        x = decode._ffn(lp, x, cfg)
        return x, (ck_l, cv_l)

    x, (ck, cv) = lax.scan(layer, x,
                           (params["blocks"], cache["k"], cache["v"]))
    return decode._final_logits(params, x, cfg), {"k": ck, "v": cv}


def _same_logits_and_pool(new, old):
    """(logits, cache) of the span-by-span body against an oracle that
    takes one softmax over the whole width.  The merge takes the
    softmax's sums in another order, so from the first attention on the
    activations differ by float32 rounding: the logits are held to
    that, and so is what the deeper layers write.  The first layer's
    K and V are computed before any attention: byte-equal."""
    np.testing.assert_allclose(np.asarray(new[0]), np.asarray(old[0]),
                               rtol=1e-5, atol=1e-5)
    for n in ("k", "v"):
        got, want = np.asarray(new[1][n]), np.asarray(old[1][n])
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("vector_pos", [False, True],
                         ids=["scalar_pos", "vector_pos"])
@pytest.mark.parametrize("t", [1, 4, 8])
@pytest.mark.parametrize("cfg", [GPT_CFG, LLAMA_CFG], ids=["gpt", "llama"])
def test_paged_pool_as_carry_keeps_the_sliced_bodys_bytes(cfg, t, vector_pos):
    """The pool held in the scan's carry gives the same bytes as the
    per-layer slices did: logits, K and V, through a permuted block
    table over a pool that already holds something; and in every layer
    the pages outside the written set stay as they were."""
    params = _params(cfg)
    psz, nblk, B = 4, 6, 2
    shape = decode.init_paged_cache(cfg, 2 * nblk + 1, psz)["k"].shape
    pool = {n: jax.random.normal(jax.random.PRNGKey(s), shape, cfg.dtype)
            for n, s in (("k", 60), ("v", 61))}
    tables = jnp.asarray([[11, 3, 9, 1, 7, 5], [2, 12, 6, 4, 10, 8]],
                         jnp.int32)
    tokens = jax.random.randint(jax.random.PRNGKey(62), (B, t), 1,
                                cfg.vocab_size)
    starts = np.asarray([5, 13], np.int32)       # row 1 straddles pages
    pos = jnp.asarray(starts) if vector_pos else jnp.int32(starts[0])
    new, old = (jax.jit(f, static_argnums=5)(params, tokens, pos, pool,
                                             tables, cfg)
                for f in (decode.paged_chunk_step, _paged_chunk_step_sliced))
    _same_logits_and_pool(new, old)
    cols = (starts if vector_pos else starts[:1].repeat(B))[:, None] \
        + np.arange(t)
    written = set(np.take_along_axis(np.asarray(tables), cols // psz,
                                     axis=1).ravel().tolist())
    untouched = [p for p in range(shape[1]) if p not in written]
    assert len(untouched) >= shape[1] - 2 * 3
    for n in ("k", "v"):
        got, was = np.asarray(new[1][n]), np.asarray(pool[n])
        np.testing.assert_array_equal(got[:, untouched], was[:, untouched])
        assert (got[:, sorted(written)] != was[:, sorted(written)]).any(
            axis=(1, 2, 3, 4)).all()             # every layer wrote


def _paged_chunk_step_gathered(params, tokens, pos, cache, block_tables,
                               cfg, pad_lo=None):
    """FROZEN copy of the dense paged_chunk_step as it stood from PR 26
    to PR 28: the pool in the scan's carry, every row's whole table
    gathered to the virtual width nblk*page in every layer and one
    softmax over it.  The oracle of the span-by-span body; used by
    nothing else."""
    from jax import lax
    B, t = tokens.shape
    psz = cache["k"].shape[2]
    S = block_tables.shape[1] * psz
    pos = jnp.asarray(pos, jnp.int32)
    cols = jnp.broadcast_to(
        jnp.reshape(pos, (-1, 1)) + jnp.arange(t)[None, :], (B, t))
    if pad_lo is None:
        pad_lo = jnp.zeros((B,), jnp.int32)
    positions = cols - pad_lo[:, None]
    x = decode._embed(params, tokens, positions, cfg)
    w_pages = jnp.take_along_axis(block_tables, cols // psz, axis=1)
    w_offs = cols % psz
    kcols = jnp.arange(S)
    mask = (kcols[None, None, :] <= cols[:, :, None]) \
        & (kcols[None, None, :] >= pad_lo[:, None, None])
    Hkv, Dh = cache["k"].shape[3:]

    def layer(carry, inputs):
        x, ck_all, cv_all = carry                # [L, P, psz, Hkv, Dh]
        lp, l = inputs
        h = decode._rmsnorm(x, lp["ln1"])
        q, k, v = decode._qkv(lp, h, positions, cfg)
        ck_all = ck_all.at[l, w_pages, w_offs].set(k.astype(ck_all.dtype))
        cv_all = cv_all.at[l, w_pages, w_offs].set(v.astype(cv_all.dtype))
        ck = ck_all[l, block_tables].reshape(B, S, Hkv, Dh)
        cv = cv_all[l, block_tables].reshape(B, S, Hkv, Dh)
        rep = q.shape[2] // Hkv
        qg = q.reshape(B, t, Hkv, rep, Dh)
        scores = jnp.einsum("bqgrk,bsgk->bgrqs", qg.astype(jnp.float32),
                            ck.astype(jnp.float32)) \
            * cfg.head_dim ** -0.5
        scores = jnp.where(mask[:, None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bgrqs,bsgk->bqgrk", probs.astype(cv.dtype), cv)
        out = out.reshape(B, t, q.shape[2], Dh)
        x = x + decode._attn_out(lp, out, cfg)
        x = decode._ffn(lp, x, cfg)
        return (x, ck_all, cv_all), None

    (x, ck, cv), _ = lax.scan(
        layer, (x, cache["k"], cache["v"]),
        (params["blocks"], jnp.arange(cache["k"].shape[0])))
    return decode._final_logits(params, x, cfg), {"k": ck, "v": cv}


def _span_of(monkeypatch, pool, rows, nblk, blocks):
    """Make a span of the dense step `blocks` table entries wide for
    calls of `rows` rows (at the tests' sizes the whole table is far
    under the bytes a span is sized from)."""
    _, _, psz, hkv, dh = pool["k"].shape
    key_bytes = rows * psz * hkv * dh * pool["k"].dtype.itemsize
    monkeypatch.setattr(decode, "_SPAN_BYTES", blocks * key_bytes)
    assert decode.paged_span_blocks(key_bytes, psz, nblk) == blocks


# Spans of 3 blocks = 12 columns.  name: (cfg, blocks a row, t, pos
# (a scalar or one a row), pad_lo or None, rows on the trash page)
SPAN_CASES = {
    "depths_far_apart": (LLAMA_CFG, 7, 1, [1, 25], None, ()),
    "last_col_of_span": (LLAMA_CFG, 7, 1, 11, None, ()),
    "first_col_of_next_span": (LLAMA_CFG, 7, 1, 12, None, ()),
    "second_col_of_next_span": (GPT_CFG, 7, 1, 13, None, ()),
    "chunk_ends_at_span_edge": (LLAMA_CFG, 7, 4, 8, None, ()),
    "chunk_straddles_span_edge": (GPT_CFG, 7, 4, [10, 22], None, ()),
    "chunk_to_the_last_col_ragged_tail": (LLAMA_CFG, 7, 4, [24, 3], None,
                                          ()),
    "tick_at_the_last_col_ragged_tail": (GPT_CFG, 7, 1, [27, 0, 14], None,
                                         ()),
    "chunk_to_the_last_col_whole_spans": (LLAMA_CFG, 6, 8, 16, None, ()),
    "chunk_of_8_scalar_pos": (GPT_CFG, 7, 8, 5, None, ()),
    "chunk_of_8_vector_pos": (LLAMA_CFG, 7, 8, [5, 17], None, ()),
    "pad_lo_inside_first_span": (GPT_CFG, 7, 1, [9, 20], [3, 7], ()),
    "pad_lo_past_first_span": (LLAMA_CFG, 7, 4, [15, 21], [2, 14], ()),
    "inactive_row_on_trash_page": (LLAMA_CFG, 7, 1, [0, 17, 6], None, (0,)),
    "all_rows_inactive": (GPT_CFG, 7, 1, [0, 0], None, (0, 1)),
}


@pytest.mark.parametrize("case", list(SPAN_CASES))
def test_paged_span_attention_matches_the_one_shot_body(case, monkeypatch):
    """Attention walked span by span up to the deepest row gives the
    logits of one softmax over the whole virtual width (float32
    rounding: the sums are taken in another order), byte-equal K and V,
    and leaves every page outside the written set as it was."""
    cfg, nblk, t, pos, pad_lo, inactive = SPAN_CASES[case]
    params = _params(cfg)
    psz, span = 4, 3
    B = len(pos) if isinstance(pos, list) else 2
    shape = decode.init_paged_cache(cfg, B * nblk + 1, psz)["k"].shape
    pool = {n: jax.random.normal(jax.random.PRNGKey(s), shape, cfg.dtype)
            for n, s in (("k", 70), ("v", 71))}
    _span_of(monkeypatch, pool, B, nblk, span)
    tables = 1 + np.random.default_rng(72).permutation(B * nblk).reshape(
        B, nblk).astype(np.int32)
    for b in inactive:
        tables[b] = 0
    tokens = jax.random.randint(jax.random.PRNGKey(73), (B, t), 1,
                                cfg.vocab_size)
    pos_arg = jnp.asarray(pos, jnp.int32)
    pad = None if pad_lo is None else jnp.asarray(pad_lo, jnp.int32)
    # a function object of its own each time: a trace made under
    # another span size is never reused
    new, old = (jax.jit(lambda *a, f=f: f(*a, cfg, pad_lo=pad))(
        params, tokens, pos_arg, pool, jnp.asarray(tables))
        for f in (decode.paged_chunk_step, _paged_chunk_step_gathered))
    _same_logits_and_pool(new, old)
    assert np.isfinite(np.asarray(new[0])).all()
    cols = np.broadcast_to(np.reshape(pos, (-1, 1)), (B, 1)) + np.arange(t)
    written = set(np.take_along_axis(tables, cols // psz, axis=1).ravel()
                  .tolist())
    untouched = [p for p in range(shape[1]) if p not in written]
    for n in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(new[1][n])[:, untouched],
                                      np.asarray(pool[n])[:, untouched])


# ---------------------------------------------------------------------------
# Engine: the parity property sweep


@pytest.mark.parametrize("speculate", [0, 3], ids=["spec_off", "spec_on"])
def test_paged_parity_sweep_prefix_hits_and_speculation(speculate):
    """THE acceptance property: random arrival schedules x {full prefix
    hit, partial hit, miss, hit+extension, repetitive} — with and
    without in-engine speculation — all bit-identical to per-prompt
    greedy generate().  The warm request populates the radix cache, so
    later identical prompts take the shared-page path."""
    base = _prompt(123, 12)                # 3 full pages at page_size 4
    prompts = {
        "warm_miss": list(base),
        "full_hit": list(base),
        "partial_hit": base[:8] + _prompt(5, 4),
        "miss": _prompt(9, 10),
        "hit_extension": base + _prompt(11, 5),
        "repetitive": [5, 6, 7] * 4,       # prompt-lookup drafts fire
    }
    max_new = 8
    oracles = {k: _oracle(GPT_PARAMS, GPT_CFG, p, max_new)
               for k, p in prompts.items()}
    rng = random.Random(speculate)

    async def run():
        # ngram=1 so drafts actually FIRE against the real model (its
        # greedy chains repeat tokens within a few steps); most drafts
        # are then rejected by verification, which is exactly the hard
        # half of the parity property.
        eng = GenerationEngine(GPT_PARAMS, GPT_CFG, speculate_k=speculate,
                               speculate_ngram=1, **PAGED_KW)
        with eng:
            warm = eng.submit(prompts["warm_miss"], max_new_tokens=max_new)
            outs = {"warm_miss": [t async for t in warm]}
            order = [k for k in prompts if k != "warm_miss"]
            rng.shuffle(order)
            streams = {}
            for k in order:                # staggered random arrivals
                streams[k] = eng.submit(prompts[k], max_new_tokens=max_new)
                await asyncio.sleep(rng.random() * 0.05)
            for k in order:
                outs[k] = await streams[k].collect()
            st = eng.stats()
        return outs, st

    outs, st = asyncio.run(run())
    for k, want in oracles.items():
        np.testing.assert_array_equal(
            np.asarray(outs[k]), want,
            err_msg=f"case {k} diverged (speculate_k={speculate})")
    # full_hit, partial_hit, and hit_extension all matched cached pages
    assert st.prefix_cache_hits >= 3, st
    assert st.prefix_hit_tokens >= 8 + 8 + 12, st
    assert st.requests_completed == len(prompts)
    if speculate:
        assert st.spec_drafted_tokens > 0, st


def test_engine_admission_bounded_by_free_pages_not_rows():
    """num_slots rows available but a pool too small for all of them:
    admission must wait for pages, peak concurrency is page-bounded,
    and everything still completes with parity."""
    prompts = [_prompt(60 + i, 6) for i in range(4)]
    oracles = [_oracle(GPT_PARAMS, GPT_CFG, p, 6) for p in prompts]

    async def run():
        # 6+6 tokens -> 3 pages of 4 each; 6 usable pages -> 2 resident
        eng = GenerationEngine(GPT_PARAMS, GPT_CFG, num_slots=3,
                               max_seq=48, prefill_chunk=5, page_size=4,
                               kv_pages=6, enable_prefix_cache=False)
        peak = 0
        with eng:
            streams = [eng.submit(p, max_new_tokens=6) for p in prompts]
            outs = []
            for s in streams:
                outs.append(await s.collect())
                peak = max(peak, eng.stats().active_slots)
            end = eng.stats()
        return outs, peak, end

    outs, peak, end = asyncio.run(run())
    for got, want in zip(outs, oracles):
        np.testing.assert_array_equal(np.asarray(got), want)
    assert peak <= 2, peak                 # pages bind before rows
    assert end.requests_completed == 4
    assert end.kv_blocks_free == end.kv_blocks_total  # prefix cache off


def test_evicting_one_sharer_keeps_shared_pages_alive():
    """Two requests share prefix pages through the radix cache; the
    first finishing (and a forced cache eviction) must not corrupt the
    second mid-generation — the allocator refcount is what stands
    between them."""
    base = _prompt(77, 12)

    async def run():
        eng = GenerationEngine(GPT_PARAMS, GPT_CFG, **PAGED_KW)
        with eng:
            await eng.generate(base, max_new_tokens=4)  # warm the cache
            a = eng.submit(base, max_new_tokens=20)
            first = await a.__anext__()    # A resident, holding shares
            b = eng.submit(base, max_new_tokens=6)
            got_b = await b.collect()      # B shares A's prefix pages
            # force the tree to drop every node NOW; A must keep going
            # on its refcounted hold alone
            eng._prefix.evict(eng.kv_pages)
            got_a = [first] + [t async for t in a]
        return got_a, got_b

    got_a, got_b = asyncio.run(run())
    np.testing.assert_array_equal(
        np.asarray(got_a), _oracle(GPT_PARAMS, GPT_CFG, base, 20))
    np.testing.assert_array_equal(
        np.asarray(got_b), _oracle(GPT_PARAMS, GPT_CFG, base, 6))


def test_speculation_accepts_on_predictable_continuation():
    """A zero-weight model generates token 0 forever, so every
    prompt-lookup draft comes true: the engine's fused verify must
    accept drafts (counter > 0) while emitting the exact greedy
    output."""
    zero = jax.tree_util.tree_map(jnp.zeros_like, GPT_PARAMS)
    zero["ln_f"] = jnp.ones_like(zero["ln_f"])
    prompt = [0] * 8
    want = _oracle(zero, GPT_CFG, prompt, 16)

    async def run():
        eng = GenerationEngine(zero, GPT_CFG, speculate_k=3,
                               speculate_ngram=2, **PAGED_KW)
        with eng:
            out = await eng.generate(prompt, max_new_tokens=16)
            st = eng.stats()
        return out, st

    out, st = asyncio.run(run())
    np.testing.assert_array_equal(np.asarray(out), want)
    assert st.spec_accepted_tokens > 0, st
    assert st.spec_drafted_tokens >= st.spec_accepted_tokens


# Shapes no other test of this file compiles, so the engine's programs
# are traced under the span the test sets: 11 blocks a row, 3 a span.
SPAN_KW = dict(num_slots=2, max_seq=44, prefill_chunk=5, page_size=4,
               kv_pages=30)


def test_attn_keys_gathered_follows_the_deepest_row(monkeypatch):
    """attn_keys_gathered counts, per tick and layer, whole spans up to
    the deepest row's last column for EVERY row of the call: a short
    row beside a long one gathers what the long one needs, a row that
    crosses a span's edge adds a span, a verify tick reaches 1 + k
    columns further; attn_keys_resident counts what the rows hold."""
    L, span = GPT_CFG.n_layers, 12
    _span_of(monkeypatch, decode.init_paged_cache(GPT_CFG, 31, 4), 2, 11, 3)
    eng = _parked_engine(**SPAN_KW)
    assert decode.DENSE_BODY.attn_keys_gathered(
        GPT_CFG, eng._pos, 4, 11) == 2 * L * span

    def tick(pos, actives, t=1):
        before = eng.stats()
        eng._pos[:] = pos
        eng._count_keys(actives, t)
        after = eng.stats()
        return (after.attn_keys_gathered - before.attn_keys_gathered,
                after.attn_keys_resident - before.attn_keys_resident)

    assert tick([3, 30], [0, 1]) == (2 * L * 36, (4 + 31) * L)
    assert tick([3, 0], [0]) == (2 * L * 12, 4 * L)     # one row active
    assert tick([11, 5], [0, 1]) == (2 * L * 12, (12 + 6) * L)
    assert tick([12, 5], [0, 1]) == (2 * L * 24, (13 + 6) * L)
    assert tick([9, 5], [0, 1], t=4) == (2 * L * 24, (10 + 6) * L)
    assert tick([43, 5], [0, 1]) == (2 * L * 48, (44 + 6) * L)


def test_stats_carry_attn_keys_gathered_through_ticks_and_verifies(
        monkeypatch):
    """A lone request's ticks are at known depths, so the counter is a
    sum that can be written down: plain ticks at columns 8..13, then
    the zero-weight model's verify ticks, which reach 1 + 3 columns."""
    L, span = GPT_CFG.n_layers, 12
    _span_of(monkeypatch, decode.init_paged_cache(GPT_CFG, 31, 4), 2, 11, 3)

    def spans(live):
        return -(-live // span) * span

    async def plain():
        with GenerationEngine(GPT_PARAMS, GPT_CFG, **SPAN_KW) as eng:
            await eng.generate(_prompt(80, 8), max_new_tokens=7)
            return eng.stats()

    st = asyncio.run(plain())
    # the first token comes from the prefill; six ticks at pos 8..13
    assert st.attn_keys_gathered == sum(2 * L * spans(p + 1)
                                        for p in range(8, 14))
    assert st.attn_keys_resident == sum(p + 1 for p in range(8, 14)) * L
    assert st.attn_keys_attended == st.attn_keys_resident

    zero = jax.tree_util.tree_map(jnp.zeros_like, GPT_PARAMS)
    zero["ln_f"] = jnp.ones_like(zero["ln_f"])

    async def verify():
        with GenerationEngine(zero, GPT_CFG, speculate_k=3,
                              speculate_ngram=2, **SPAN_KW) as eng:
            await eng.generate([0] * 8, max_new_tokens=13)
            return eng.stats()

    st = asyncio.run(verify())
    # the lookup drafts the one token after the latest match and it
    # comes true: six verify ticks, two tokens each, from pos 8
    assert st.spec_accepted_tokens == 6
    assert st.attn_keys_gathered == sum(2 * L * spans(p + 4)
                                        for p in range(8, 20, 2))


# ---------------------------------------------------------------------------
# The width of a prefill chunk: named by the caller, or the ridge width

# RoPE, so the table may be wider than any test's prompt: the default
# width (256) is then in force unclipped, and no chunk below reaches the
# table's last width.
WIDE_CFG = llama.LlamaConfig(vocab_size=97, d_model=32, n_heads=4,
                             n_kv_heads=2, n_layers=2, d_ff=48,
                             max_seq=1024, dtype=jnp.float32,
                             remat=False, use_flash=False)
WIDE_PARAMS = _params(WIDE_CFG)
WIDE_KW = dict(num_slots=2, max_seq=1024, page_size=4, kv_pages=600,
               kv_tiering=False)
SHARED = 12   # tokens of the warm prompt a later one shares: 3 pages


def _greedy_cache_free(prompt, n):
    """n greedy tokens by full forwards of the growing sequence, and the
    logits the first of them was taken from."""
    seq, first = list(prompt), None
    for _ in range(n):
        logits = np.asarray(llama.forward(
            WIDE_PARAMS, jnp.asarray([seq]), WIDE_CFG)[0, -1])
        first = logits if first is None else first
        seq.append(int(logits.argmax()))
    return seq[len(prompt):], first


@pytest.mark.parametrize(
    "length", ["shorter", "equal", "multiple_and_rest", "hit_mid_width"])
@pytest.mark.parametrize("chunk", [8, 32, None],
                         ids=["chunk8", "chunk32", "default"])
def test_prefill_chunk_width_keeps_results_and_counts_its_padding(
        chunk, length, monkeypatch):
    """Whatever the width of a chunk, named or the default, a prompt's
    greedy tokens and the logits its first token is taken from are the
    cache-free forward's; the prompt costs ceil(tokens left to prefill /
    width) chunks, `prefill_tokens` gains those tokens and
    `prefill_pad_tokens` the columns the chunks computed beside them."""
    seen = []

    with GenerationEngine(WIDE_PARAMS, WIDE_CFG, prefill_chunk=chunk,
                          **WIDE_KW) as eng:
        width = eng.prefill_chunk
        assert width == (chunk or engine_mod._RIDGE_CHUNK)
        sample = eng._sample_host
        monkeypatch.setattr(
            eng, "_sample_host",
            lambda row, req: seen.append(row.copy()) or sample(row, req))
        L = {"shorter": width - 3, "equal": width,
             "multiple_and_rest": 2 * width + 3,
             "hit_mid_width": SHARED + width + 5}[length]
        prompt = _prompt(900 + L, L, WIDE_CFG)
        matched = 0
        if length == "hit_mid_width":
            # the warm prompt leaves SHARED tokens' pages in the prefix
            # cache, so the next one starts at column 12: inside a
            # chunk's width for every width here
            warm = prompt[:SHARED] + _prompt(7, 3, WIDE_CFG)
            eng.submit(warm, max_new_tokens=1).result(timeout=120)
            matched = SHARED
            seen.clear()
        s0 = eng.stats()
        got = eng.submit(prompt, max_new_tokens=3).result(timeout=120)
        s1 = eng.stats()

    want, first = _greedy_cache_free(prompt, 3)
    assert list(got) == want
    np.testing.assert_allclose(seen[0], first, atol=2e-4, rtol=0)
    chunks = -(-(L - matched) // width)
    assert s1.prefix_hit_tokens - s0.prefix_hit_tokens == matched
    assert s1.loop_turns_with_chunk - s0.loop_turns_with_chunk == chunks
    assert s1.prefill_tokens - s0.prefill_tokens == L - matched
    assert s1.prefill_pad_tokens - s0.prefill_pad_tokens == \
        chunks * width - (L - matched)


def test_default_prefill_chunk_is_one_program_whatever_the_prompt():
    """An engine built with no `prefill_chunk` has ONE prefill program:
    the width its attribute holds (an int), compiled at start-up;
    prompts of many lengths, padded or several chunks long, compile
    nothing more while they end before the table's last width."""
    programs = engine_mod._prefill_chunk._cache_size()
    # drawn first: the counter is the process's, and jax draws a prompt
    prompts = [_prompt(n, n, WIDE_CFG)
               for n in (9, 1, 5, 255, 256, 257, 300, 511, 512, 700)]
    with GenerationEngine(WIDE_PARAMS, WIDE_CFG, **WIDE_KW) as eng:
        assert type(eng.prefill_chunk) is int
        assert eng.prefill_chunk == engine_mod._RIDGE_CHUNK == 256
        # start-up compiled the tick and the chunk; the first request
        # compiles what the host does around them
        eng.submit(prompts[0], max_new_tokens=2).result(timeout=120)
        warm = eng.stats()
        for prompt in prompts[1:]:
            eng.submit(prompt, max_new_tokens=2).result(timeout=120)
        st = eng.stats()
    assert st.jit_compiles == warm.jit_compiles
    assert engine_mod._prefill_chunk._cache_size() <= programs + 1
    assert st.loop_turns_with_chunk - warm.loop_turns_with_chunk == \
        1 + 1 + 1 + 1 + 2 + 2 + 2 + 2 + 3
    # clipped to the table where the table is narrower than the ridge
    small = _parked_engine(num_slots=1, max_seq=48, page_size=4)
    assert small.prefill_chunk == small._s_virt == 48


# ---------------------------------------------------------------------------
# Structured backpressure


def _parked_engine(**kw):
    """An engine whose worker is parked so admission state is
    deterministic (same trick as the HTTP 503 test)."""
    eng = GenerationEngine(GPT_PARAMS, GPT_CFG, **kw)
    eng.stop()
    eng.start = lambda: eng
    return eng


def test_submit_distinguishes_queue_full_from_kv_exhausted():
    # kv_exhausted: commit cap = 1.0 * 6 pages; each request wants
    # 3 pages (6+6 tokens at page 4) -> the third submit overflows the
    # cap long before the 50-deep queue fills.
    eng = _parked_engine(num_slots=2, max_seq=48, prefill_chunk=5,
                         page_size=4, kv_pages=6, max_queue_len=50,
                         kv_commit_factor=1.0)
    eng.submit(_prompt(1, 6), max_new_tokens=6)
    eng.submit(_prompt(2, 6), max_new_tokens=6)
    with pytest.raises(EngineOverloadedError) as ei:
        eng.submit(_prompt(3, 6), max_new_tokens=6)
    assert ei.value.reason == "kv_exhausted"
    assert ei.value.retry_after_s > 1.0
    assert eng.stats().requests_rejected == 1

    # queue_full: huge commit headroom, 1-deep queue.
    eng2 = _parked_engine(num_slots=2, max_seq=48, prefill_chunk=5,
                          page_size=4, kv_pages=40, max_queue_len=1,
                          kv_commit_factor=100.0)
    eng2.submit(_prompt(4, 6), max_new_tokens=6)
    with pytest.raises(EngineOverloadedError) as ei:
        eng2.submit(_prompt(5, 6), max_new_tokens=6)
    assert ei.value.reason == "queue_full"
    assert ei.value.retry_after_s == 1.0

    # a request the pool can NEVER hold is a caller error, not overload
    with pytest.raises(ValueError, match="KV pages"):
        eng.submit(_prompt(6, 20), max_new_tokens=20)


def test_commit_cap_releases_as_requests_finish():
    async def run():
        # identical shapes to the admission-bounded test above, so the
        # two share every jit compilation
        eng = GenerationEngine(GPT_PARAMS, GPT_CFG, num_slots=3,
                               max_seq=48, prefill_chunk=5, page_size=4,
                               kv_pages=6, max_queue_len=50,
                               kv_commit_factor=1.0,
                               enable_prefix_cache=False)
        with eng:
            await eng.generate(_prompt(1, 6), max_new_tokens=6)
            await eng.generate(_prompt(2, 6), max_new_tokens=6)
            # both finished -> demand released -> admission open again
            out = await eng.generate(_prompt(3, 6), max_new_tokens=6)
        return out

    assert len(asyncio.run(run())) == 6


def test_http_retry_after_tracks_overload_reason():
    """api.py maps queue_full -> Retry-After 1 and kv_exhausted -> a
    longer hint, both as structured 503s.  Float seconds on the wire:
    a tier-aware hint can be sub-second (one demotion sweep away) and
    integer rounding would turn it into a full second of idle client."""
    import json

    from ray_tpu.serve._private.replica import Request
    from ray_tpu.serve.llm.api import LLMServer

    def _call(srv):
        async def go():
            req = Request(method="POST", path="/", body=json.dumps(
                {"tokens": _prompt(7, 6), "max_new_tokens": 6}).encode())
            return await srv(req)
        return asyncio.run(go())

    srv = LLMServer(lambda: (GPT_PARAMS, GPT_CFG), engine_config=dict(
        num_slots=2, max_seq=48, prefill_chunk=5, page_size=4,
        kv_pages=6, max_queue_len=50, kv_commit_factor=1.0))
    try:
        srv.engine.stop()
        srv.engine.start = lambda: srv.engine
        srv.engine.submit(_prompt(1, 6), max_new_tokens=6)
        srv.engine.submit(_prompt(2, 6), max_new_tokens=6)
        out = _call(srv)
        assert out["__http__"] is True and out["status"] == 503
        assert ("Retry-After", "5.000") in out["headers"], out["headers"]
    finally:
        srv.engine.stop()

    srv2 = LLMServer(lambda: (GPT_PARAMS, GPT_CFG), engine_config=dict(
        num_slots=2, max_seq=48, prefill_chunk=5, page_size=4,
        kv_pages=40, max_queue_len=1))
    try:
        srv2.engine.stop()
        srv2.engine.start = lambda: srv2.engine
        srv2.engine.submit(_prompt(1, 6), max_new_tokens=6)
        out = _call(srv2)
        assert out["__http__"] is True and out["status"] == 503
        assert ("Retry-After", "1.000") in out["headers"], out["headers"]
    finally:
        srv2.engine.stop()


# ---------------------------------------------------------------------------
# Observability


def test_paged_metrics_exported_via_prometheus():
    async def run():
        eng = GenerationEngine(GPT_PARAMS, GPT_CFG, name="pagedprom",
                               speculate_k=3, speculate_ngram=2,
                               **PAGED_KW)
        with eng:
            await eng.generate(_prompt(99, 9), max_new_tokens=6)
            await eng.generate(_prompt(99, 9), max_new_tokens=6)
            st = eng.stats()
        return st

    st = asyncio.run(run())
    assert st.prefix_cache_hits >= 1 and st.prefix_cache_misses >= 1
    assert st.kv_blocks_total == PAGED_KW["kv_pages"]
    # completed requests release their holds; only radix-held prompt
    # pages stay out of the free list
    tree_held = 2 * (9 // PAGED_KW["page_size"])  # two cached prompts..
    assert st.kv_blocks_free >= st.kv_blocks_total - tree_held

    from ray_tpu.util.metrics import prometheus_text, registry_snapshot
    text = prometheus_text(registry_snapshot())
    for needle in ("serve_llm_kv_blocks_total",
                   "serve_llm_kv_blocks_free",
                   "serve_llm_prefix_cache_hits_total",
                   "serve_llm_prefix_cache_misses_total",
                   "serve_llm_spec_accepted_tokens_total"):
        assert needle in text, needle
    assert 'engine="pagedprom"' in text


def test_stats_surface_paging_fields_through_server():
    from ray_tpu.serve.llm.api import LLMServer
    srv = LLMServer(lambda: (GPT_PARAMS, GPT_CFG),
                    engine_config=dict(PAGED_KW))
    try:
        st = srv.stats()
        for key in ("kv_blocks_total", "kv_blocks_free", "page_size",
                    "prefix_cache_hits", "prefix_cache_misses",
                    "spec_accepted_tokens"):
            assert key in st, key
        assert st["page_size"] == PAGED_KW["page_size"]
    finally:
        srv.engine.stop()
