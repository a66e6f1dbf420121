"""A prefill chunk's block-sparse attention over pages, a QUERY BLOCK at
a time (`query_block_attention`): the tokens of one page of the chunk,
times the query heads of a KV group, are scored against each page of the
row's context ONCE, under a mask that says how many of a page's keys
each token sees (none: the token did not choose the page; all of them;
or, in the block's own page, those up to the token).

The pool stays as the model keeps it, [layers, P, G, page, Dh], in HBM:
the kernel is handed the whole of it and the layer's index as a
prefetched scalar, as `ops/paged_attention.py` is, and shares that
kernel's walk: block-table entries are prefetched scalars, a page of
keys and one of values (both KV groups, contiguous) come into VMEM with
one DMA each, two trips of `_TRIP_PAGES` pages in flight (the next
trip's copies, or the next query block's first, are started before this
trip's are waited for), and a running softmax (maximum, sum, float32
accumulator) lives in VMEM.  One instance of the kernel is one query
block.  The trips it walks are listed outside and prefetched: a trip
none of whose pages any token of the block sees is not on the list, so
it is neither copied nor scored.

Scores are kept TRANSPOSED, [keys, query rows]: a page's mask is then one
row of `visible` broadcast down the sublanes of the page's 64 key rows
(the other way round it is a column spread over lanes, once a page), the
softmax's maximum and sum run down the sublanes, and the statistics are
lane-dense [1, rows] vectors.  The weighted sum is taken transposed too,
[Dh, rows] = V^T P, and laid back by the caller.

What it costs and when it stops paying.  Every token of a query block is
scored against every page ANY of them sees: at 64 tokens x (topk - forced
= 31 free choices) over the ~225 pages of a 16k context the union is
nearly all pages, so a (block, group) multiplies 2 x 2 x 1,024 x context
x 128 (8.6 GFLOP at 16k, 44 us at a v5e's peak) where the chosen 64
pages alone need a twelfth of that, and reads each page once (8 MB at
16k, 10 us).  It is bought because 1,024-row products on the MXU are
cheaper than the alternative at these contexts.  Measured on a v5e
(PERF.md section 6, PR 56), a layer's call of 8 query blocks takes 0.19 ms
+ 0.071 ms for each 1,024 tokens of context: 0.77 / 1.20 / 2.48 ms after
8k / 14k / 32k.  A walk a (token, group) at a time would score 16 rows (an
eighth of the MXU's) against its own 31 free pages and copy them for every
token: 1 GB a layer and chunk whatever the context (1.3 ms at the HBM's
peak) in 1,024 walks of at least two trips (~0.66 us each beside the
copies, `ops/paged_attention.py`), so 2-3 ms a layer by estimate; it is
not built.  The two cross between 25k and 40k tokens of context: where
`max_seq` goes well past that (no cell's does: 33,792), the per-token
walk, or a query block narrower than a page, is the one to build.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Pages one trip of a query block's walk copies, scores and merges
# (both KV groups of each).  Measured on a v5e (PERF.md section 6, PR 56):
# MiniCPM-SALA's call of a layer (8 query blocks of 64 tokens x 32 heads,
# pages of 2 x 64 x 128 bf16, 64 chosen pages a token) reads 0.77 / 1.20 /
# 2.48 ms at 8 pages a trip after 8k / 14k / 32k tokens of context, 0.77 /
# 1.20 / 2.41 at 16 and 0.82 / 1.31 / 2.75 at 4.
_TRIP_PAGES = 8


def _kernel(layer_ref, bt_ref, order_ref, runs_ref, first_ref, q_ref,
            vis_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, m_ref, l_ref,
            acc_ref, *, pages: int, nblk: int, trips: int, scale: float):
    b = pl.program_id(0)
    blocks = pl.num_programs(0)
    layer = layer_ref[0]
    G, rows, _ = q_ref.shape
    psz = kbuf.shape[2] // pages
    runs = runs_ref[b]

    def copies(blk, i, slot):
        """The DMAs of the `i`-th listed trip of query block `blk`."""
        j = order_ref[blk * trips + i]
        out = []
        for p in range(pages):
            # (past the table's end: any page; none of its keys is seen)
            page = bt_ref[jnp.minimum(j * pages + p, nblk - 1)]
            at = pl.ds(p * psz, psz)
            out.append(pltpu.make_async_copy(
                k_hbm.at[layer, page], kbuf.at[slot, :, at], sems.at[0, slot]))
            out.append(pltpu.make_async_copy(
                v_hbm.at[layer, page], vbuf.at[slot, :, at], sems.at[1, slot]))
        return out

    def start(blk, i, slot):
        for c in copies(blk, i, slot):
            c.start()

    @pl.when(b == 0)
    def _():
        start(0, 0, 0)

    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    key = lax.broadcasted_iota(jnp.int32, (psz, rows), 0)

    def trip(i, carry):
        slot = (first_ref[b] + i) % 2

        @pl.when(i + 1 < runs)
        def _():
            start(b, i + 1, 1 - slot)

        @pl.when((i + 1 == runs) & (b + 1 < blocks))
        def _():
            start(b + 1, 0, 1 - slot)

        for c in copies(b, i, slot):
            c.wait()
        j = order_ref[b * trips + i]
        for g in range(G):
            s = lax.dot_general(kbuf[slot, g], q_ref[g],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            s = jnp.concatenate([
                jnp.where(key < vis_ref[g, pl.ds(j * pages + p, 1), :],
                          s[p * psz:(p + 1) * psz], -jnp.inf)
                for p in range(pages)])                     # [keys, rows]
            top = jnp.maximum(m_ref[g], s.max(0, keepdims=True))
            # (a token that sees nothing yet: no maximum to subtract)
            ref = jnp.where(top == -jnp.inf, 0.0, top)
            old = jnp.exp(m_ref[g] - ref)
            e = jnp.exp(s - ref)
            m_ref[g] = top
            l_ref[g] = old * l_ref[g] + e.sum(0, keepdims=True)
            acc_ref[g] = old * acc_ref[g] + lax.dot_general(
                vbuf[slot, g], e.astype(vbuf.dtype),
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # [Dh, rows]
        return carry

    lax.fori_loop(0, runs, trip, 0)
    o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def query_block_attention(q, k_pool, v_pool, layer, block_table, visible, *,
                          interpret: bool = False):
    """q [T, H, Dh], one row's chunk of T tokens (whole pages), over the
    pages of layer `layer` of k_pool / v_pool [L, P, G, page, Dh] that
    block_table [nblk] names.  visible [T, G, nblk] int32: of page n of
    the row's context, token t's KV group g attends to the first
    visible[t, g, n] keys (0: the page is not one of its blocks; `page`:
    all of it; fewer in the token's own page, where a key past the token
    is not seen).  Every token sees a key.  One softmax a (token, head)
    over all it sees; matmul inputs are the pool's dtype, the softmax and
    the accumulator float32.  Returns [T, H, Dh] in q's dtype."""
    T, H, Dh = q.shape
    G, psz = k_pool.shape[2:4]
    nblk = block_table.shape[0]
    R, blocks = H // G, T // psz
    rows = psz * R
    pages = min(_TRIP_PAGES, nblk)
    trips = -(-nblk // pages)

    # a query block's rows are (token, head of the group), the head minor
    qb = q.reshape(blocks, psz, G, R, Dh).swapaxes(1, 2).reshape(
        blocks, G, rows, Dh)
    vis = jnp.pad(visible.astype(jnp.int32),
                  ((0, 0), (0, 0), (0, trips * pages - nblk)))
    vis = vis.reshape(blocks, psz, G, trips * pages)
    # the trips a query block walks: those with a page any token of it
    # sees, in order
    wanted = (vis.reshape(blocks, psz, G, trips, pages) > 0).any((1, 2, 4))
    runs = wanted.sum(1).astype(jnp.int32)
    place = jnp.where(wanted, jnp.cumsum(wanted, axis=1) - 1, trips)
    order = jnp.zeros((blocks, trips), jnp.int32).at[
        jnp.arange(blocks)[:, None], place].set(
            jnp.arange(trips, dtype=jnp.int32)[None, :], mode="drop")
    # the buffer a block's first trip lands in: trips alternate between
    # the two through the whole call
    first = (jnp.cumsum(runs) - runs) % 2
    vis = jnp.repeat(vis.transpose(0, 2, 3, 1), R, axis=3)  # [.., page, rows]

    block = lambda *shape: pl.BlockSpec(  # noqa: E731
        (None,) + shape, lambda b, *_: (b, 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, pages=pages, nblk=nblk, trips=trips,
                          scale=Dh ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(blocks,),
            in_specs=[block(G, rows, Dh), block(G, trips * pages, rows),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=block(G, Dh, rows),
            scratch_shapes=[
                pltpu.VMEM((2, G, pages * psz, Dh), k_pool.dtype),
                pltpu.VMEM((2, G, pages * psz, Dh), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((G, 1, rows), jnp.float32),
                pltpu.VMEM((G, 1, rows), jnp.float32),
                pltpu.VMEM((G, Dh, rows), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((blocks, G, Dh, rows), q.dtype),
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 << 20),
        name="query_block_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      block_table.astype(jnp.int32), order.reshape(-1), runs,
      first.astype(jnp.int32), qb, vis, k_pool, v_pool)
    # [blocks, G, Dh, (token, head)] -> [T, H, Dh]
    return out.reshape(blocks, G, Dh, psz, R).transpose(0, 3, 1, 4, 2).reshape(
        T, H, Dh)
