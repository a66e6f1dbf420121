"""A decode tick's attention over pages, ragged: each row of the call
reads ITS OWN pages up to ITS OWN position, whatever the deepest row of
the call holds (`paged_attention`).

The pool stays as the model keeps it, [layers, P, page, ...], in HBM: the
kernel is handed the whole of it and the layer's index as a prefetched
scalar (as `ops/ssm.step_pallas` is handed every layer's states), so no
layer is sliced out and nothing is copied or re-laid.  One instance of
the kernel is one row.  It walks the row's block-table entries (a
prefetched scalar array, as its position is) in blocks of `_BLOCK_KEYS`
keys (more of a thin token's: `block_pages`), copies each page of keys
and of values of a block from HBM into a VMEM buffer with one DMA a page
and pool, two blocks in flight (the next block's copies, or the next
ROW's first, are started before this block's are waited for), and keeps
a running softmax (maximum, sum, float32 accumulator) in VMEM.  A row
costs its own blocks and nothing for the table's width: the walk's trip
count is read from the row's position.  A row at position 0 is IDLE (the
engine's convention: a live row has a prompt behind it; its table is on
the trash page) and holds nothing, so it reads nothing: no trip, no
copy (the chain of first copies passes over it to the next live row),
and its output is zeros.

A token's heads reach the kernel in one of the three forms the models
keep them in (`models/exaone_moe._kept`, `models/deepseek_v2._lat_row`),
told apart by what the call hands in, the pool's shape and whether
there is a pool of values at all:

  [P, page, G, Dh]   heads in ROWS (K-EXAONE's [8, 128], and the dense
                     body's pool of `models/decode.py`, Mistral's and
                     InternLM2's [8, 128]): a page is read
                     as [page x G, Dh], one row a (token, head), which
                     is the array as it lies.  Every query head is
                     scored against every row, and a row of another
                     key-value head is masked: G x the multiplies, no
                     slice across sublanes
  [P, page, G x Dh]  heads side by side in the LANES (MiMo's 4 x 192,
                     values 4 x 128): a page is read as [page, G x Dh].
                     A query stands in its own head's lanes of a row as
                     wide as all of them, zeros elsewhere (the caller
                     lays it so: `widen`), and the weighted sum is taken
                     of the whole value row, of which a head keeps its
                     own head's lanes: again G x the multiplies, and no
                     re-laying of a 192-wide head
  [P, page, Dh],     a LATENT page (DeepSeek-V2's [512 | 64 | 64 zeros]
  no value pool      of one head that all 128 absorbed query heads
                     share): a token's value is the first `value_width`
                     lanes of its key.  The lanes form with one head and
                     ONE pool: a page is copied once, into the one pair
                     of buffers there is, scored over all its lanes, and
                     the weighted sum is taken of a whole-tile slice of
                     the same buffer; the scale is the caller's (YaRN's
                     factor is in it)

so all are one algorithm, Q [H, Wk] against K [n, Wk] under a mask and
P [H, n] against V [n, Wv], whose tile shapes follow the head widths.
The bytes bound the call in the first two (PERF.md section 6, PR 52);
128 query heads over a 1.25 KB token make the MXU the bound of the
third (PR 53).

A position past the row's own is masked, so what a page holds behind
the row's last token, and what the trash page holds, is never seen.

A model that CHOOSES the keys a row attends to (models/glm_moe_dsa.py,
2,048 of all a row holds) hands the walk a mask, `keep` [B, S] over each
row's OWN sequence positions: in the third form (the latent page: the
others refuse it by name) a key is weighed only where `keep` is set AND
it is at or before the row's position.  The row's line of the mask
reaches VMEM beside its query, a block of the walk a row of it; every
page the row holds is still copied (that is the trade against a gather
of the chosen latents, which the caller makes by depth:
deepseek_v2.walks), a block that keeps no key merges nothing, a row that
keeps none returns zeros, and the call also returns how many keys each
row weighed, counted where the mask is applied.  `keep=None` is a static
Python branch: without a mask every form traces to the program it was
before there was one (equal jaxprs, and Mosaic modules equal byte for
byte once locations are stripped; PERF.md section 6, PR 67).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Keys one block of the walk covers (whole pages): what one trip of a
# row's loop copies, scores and merges.  Measured on a v5e (PERF.md
# section 6, PR 52): K-EXAONE's 128 rows (4 KB a token) read 2.53 ms a
# layer at 256 and 320, 2.54 at 384, 2.60 at 512, 3.03 at 128; MiMo's 64
# rows (2.5 KB a token) 1.27 at 384-512, 1.34 at 320, 1.42 at 256, 2.01
# at 128.
_BLOCK_KEYS = 384
# ...and the bytes of the pool a block holds at least.  A trip costs
# ~0.66 us whatever it holds (the two products' fill and drain, the
# reductions between them, the accumulator's rescale), which hides under
# the copy of a thick token's block and not beside a thin one's
# arithmetic: DeepSeek-V2's 64 rows of 1.25 KB latents read 0.93 ms a
# layer at 384 keys, 0.81 / 0.75 / 0.68 / 0.66 at 512 / 640 / 768 / 1,024
# (PERF.md section 6, PR 53).  What 384 of MiMo's tokens are, the
# smallest block measured on its plateau, is 768 latents.
_BLOCK_BYTES = 384 * 2560


def block_pages(page_size: int, nblk: int, token_bytes: int) -> int:
    """Whole pages one block of the walk covers, of a pool that keeps
    `token_bytes` a token and layer."""
    keys = max(_BLOCK_KEYS, _BLOCK_BYTES // token_bytes)
    return max(1, min(keys // page_size, nblk))


def _trips(pos, width: int):
    """Blocks of `width` keys the walk of each row at `pos` takes (numpy
    or traced): its own up to its position, none where it is idle."""
    return (pos // width + 1) * (pos > 0)


def keys_copied(pos, page_size: int, nblk: int, token_bytes: int) -> int:
    """Keys the kernel copies from one layer's pool for rows at `pos`
    (every row of the call, idle ones at 0): each live row's own blocks,
    the last one whole."""
    width = block_pages(page_size, nblk, token_bytes) * page_size
    return int(_trips(np.asarray(pos, np.int64), width).sum()) * width


def widen(q, n_kv_heads: int):
    """q [B, H, Dh] -> [B, H, G x Dh]: each head's query in its own
    key-value head's lanes, zeros elsewhere: what scores keys whose
    heads lie side by side as they lie."""
    B, H, Dh = q.shape
    G = n_kv_heads
    wide = jnp.einsum("bgrd,gh->bgrhd", q.reshape(B, G, H // G, Dh),
                      jnp.eye(G, dtype=q.dtype))
    return wide.reshape(B, H, G * Dh)


def _kernel(layer_ref, pos_ref, first_ref, live_ref, bt_ref, q_ref, k_hbm,
            *rest,
            pages: int, nblk: int, page_size: int, in_rows: int,
            group: int, scale: float, values_in_keys: bool, masked: bool):
    keep_ref = n_ref = None
    if masked:
        # (the latent form alone) the row's line of `keep`, a block of
        # the walk a row of it; out: the keys weighed, lane by lane
        keep_ref, o_ref, n_ref, kbuf, sems, m_ref, l_ref, acc_ref = rest
        vbuf = kbuf
        n_ref[...] = jnp.zeros_like(n_ref)
    elif values_in_keys:
        # values are the first lanes of the keys: one pool, one buffer
        o_ref, kbuf, sems, m_ref, l_ref, acc_ref = rest
        vbuf = kbuf
    else:
        v_hbm, o_ref, kbuf, vbuf, sems, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    rows = pl.num_programs(0)
    layer = layer_ref[0]
    per = kbuf.shape[1] // pages          # rows of the buffer a page fills
    width = pages * page_size             # keys a block
    H, Dv = o_ref.shape
    pos = pos_ref[b]
    trips = _trips(pos, width)

    def copies(row, j, slot):
        """The DMAs of block `j` of `row` into buffer `slot`."""
        out = []
        for p in range(pages):
            # (past the table's end: any page; every key of it is masked)
            at = jnp.minimum(j * pages + p, nblk - 1)
            page = bt_ref[row * nblk + at]
            out.append(pltpu.make_async_copy(
                k_hbm.at[layer, page], kbuf.at[slot, pl.ds(p * per, per)],
                sems.at[0, slot]))
            if not values_in_keys:
                out.append(pltpu.make_async_copy(
                    v_hbm.at[layer, page],
                    vbuf.at[slot, pl.ds(p * per, per)], sems.at[1, slot]))
        return out

    def start(row, j, slot):
        for c in copies(row, j, slot):
            c.start()

    # live_ref[i]: the first live row at or after row i (`rows`: none)
    @pl.when((b == 0) & (live_ref[0] < rows))
    def _():
        start(live_ref[0], 0, 0)

    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    q = q_ref[...]                                           # [H, Wk]
    n = kbuf.shape[1]
    col = lax.broadcasted_iota(jnp.int32, (H, n), 1)
    if in_rows > 1:
        # a row of the buffer is a (token, key-value head)
        own = col % in_rows == lax.broadcasted_iota(
            jnp.int32, (H, n), 0) // group
        col = col // in_rows
    else:
        own = None

    def block(j, carry):
        slot = (first_ref[b] + j) % 2

        @pl.when(j + 1 < trips)
        def _():
            start(b, j + 1, 1 - slot)

        @pl.when((j + 1 == trips) & (live_ref[b + 1] < rows))
        def _():
            start(live_ref[b + 1], 0, 1 - slot)

        for c in copies(b, j, slot):
            c.wait()
        s = lax.dot_general(q, kbuf[slot], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if masked:
            # one line of the block's keys, the same for every head
            weighed = (j * width + lax.broadcasted_iota(
                jnp.int32, (1, n), 1) <= pos) \
                & (keep_ref[pl.ds(j, 1), :] != 0)
            n_ref[...] += weighed.astype(jnp.int32)
            s = s + jnp.where(weighed, 0.0, -jnp.inf)
            top = jnp.maximum(m_ref[...], s.max(-1, keepdims=True))
            # (a block may hold no kept key, the row's first blocks too)
            ref = jnp.where(top > -jnp.inf, top, 0.0)
        else:
            seen = j * width + col <= pos
            if own is not None:
                seen &= own
            s = jnp.where(seen, s, -jnp.inf)
            ref = top = jnp.maximum(m_ref[...], s.max(-1, keepdims=True))
        old = jnp.exp(m_ref[...] - ref)
        e = jnp.exp(s - ref)
        m_ref[...] = top
        l_ref[...] = old * l_ref[...] + e.sum(-1, keepdims=True)
        acc_ref[...] = old * acc_ref[...] + jnp.dot(
            e.astype(vbuf.dtype), vbuf[slot, :, :acc_ref.shape[1]],
            preferred_element_type=jnp.float32)
        return carry

    lax.fori_loop(0, trips, block, 0)
    # (an idle row summed nothing: zeros over one, not over zero; nor
    # did a row none of whose keys is kept)
    total = jnp.where(l_ref[...] > 0 if masked else trips > 0,
                      l_ref[...], 1.0)
    if acc_ref.shape[1] == Dv:
        o_ref[...] = (acc_ref[...] / total).astype(o_ref.dtype)
    else:
        # of the whole value row a head keeps its own head's lanes
        for g in range(H // group):
            at = slice(g * group, (g + 1) * group)
            o_ref[at, :] = (acc_ref[at, g * Dv:(g + 1) * Dv]
                            / total[at, :]).astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, layer, block_tables, pos, *,
                    n_kv_heads: int, value_width: Optional[int] = None,
                    scale: Optional[float] = None, keep=None,
                    interpret: bool = False):
    """q [B, H, Dh] (one token a row) over the pages of layer `layer` of
    k_pool [L, P, page, G, Dh] / v_pool [L, P, page, G, Dv], or the same
    with a token's heads side by side ([L, P, page, G x Dh]): row b
    attends to positions 0..pos[b] of the pages block_tables[b] names; a
    row at position 0 is idle, reads nothing and returns zeros.
    `v_pool` None: a value is the first `value_width` lanes of its key,
    k_pool [L, P, page, Dh] of one head every query head shares.
    Scores are scaled by `scale` (default Dh ** -0.5); matmul inputs are
    the pool's dtype, the softmax and the accumulator float32.  Returns
    [B, H, Dv] in q's dtype.
    `keep` [B, S] (0 or false: not kept; S the table's width in keys),
    with keys that hold their values alone: row b attends to the kept
    among its positions 0..pos[b] and to nothing else, zeros where it
    keeps none.  Returns that and the keys each row weighed, [B] int32,
    counted where the mask is applied."""
    B, H, Dh = q.shape
    G = n_kv_heads
    L, P, psz = k_pool.shape[:3]
    nblk = block_tables.shape[1]
    if v_pool is None:
        if G != 1 or k_pool.ndim != 4 or not 0 < (value_width or 0) <= Dh:
            raise ValueError(
                f"keys that hold their values are one head of [L, P, page, "
                f"Dh] and a value width within it, got {k_pool.shape}, "
                f"{G} heads, value_width={value_width}")
        in_rows, Dv, Wv, pools = 1, value_width, value_width, [k_pool]
    else:
        if k_pool.ndim == 5:
            # (token, head) rows: the pool as it lies (a merge of the two
            # dimensions above a head's lanes moves nothing)
            in_rows, Dv = G, v_pool.shape[-1]
            k_pool = k_pool.reshape(L, P, psz * G, Dh)
            v_pool = v_pool.reshape(L, P, psz * G, Dv)
        else:
            in_rows, Dv = 1, v_pool.shape[-1] // G
            q = widen(q, G)
        pools, Wv = [k_pool, v_pool], v_pool.shape[3]
    per, Wk = k_pool.shape[2], k_pool.shape[3]
    # (the bytes a token of the pools as they lie: a page's over its tokens)
    pages = block_pages(psz, nblk, sum(
        per * pool.shape[3] * pool.dtype.itemsize for pool in pools) // psz)
    width = pages * psz
    if keep is not None:
        if v_pool is not None or keep.shape != (B, nblk * psz):
            raise ValueError(
                f"`keep` is [rows, the table's width in keys] over keys "
                f"that hold their values, got {keep.shape} for {B} rows of "
                f"{nblk * psz} keys, a value pool: {v_pool is not None}")
        # a row's line, a block of its walk a row of it (the table's
        # last block may end past the table: nothing kept there)
        lines = -(-nblk // pages)
        keep = jnp.pad(keep.astype(jnp.int32), (
            (0, 0), (0, lines * width - nblk * psz))).reshape(B, lines, width)
    pos = pos.astype(jnp.int32)
    trips = _trips(pos, width)
    # the buffer a row's first block lands in: blocks alternate between
    # the two through the whole call
    first = (jnp.cumsum(trips) - trips) % 2
    # the first live row at or after each row, and after the last: B
    live = lax.cummin(jnp.where(pos > 0, jnp.arange(B), B), reverse=True)
    live = jnp.concatenate([live, jnp.full((1,), B, live.dtype)])
    row = lambda w: pl.BlockSpec((None, H, w),  # noqa: E731
                                 lambda b, *_: (b, 0, 0))
    # a pair of buffers and a pair of semaphores a pool
    buffers = [pltpu.VMEM((2, pages * per, pool.shape[3]), pool.dtype)
               for pool in pools]
    in_specs = [row(Wk)] + [pl.BlockSpec(memory_space=pl.ANY) for _ in pools]
    out_specs, out_shape = row(Dv), jax.ShapeDtypeStruct((B, H, Dv), q.dtype)
    if keep is not None:
        in_specs.append(pl.BlockSpec((None,) + keep.shape[1:],
                                     lambda b, *_: (b, 0, 0)))
        out_specs = [out_specs, pl.BlockSpec((None, 1, width),
                                             lambda b, *_: (b, 0, 0))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((B, 1, width), jnp.int32)]
    out = pl.pallas_call(
        functools.partial(_kernel, pages=pages, nblk=nblk, page_size=psz,
                          in_rows=in_rows, group=H // G,
                          scale=Dh ** -0.5 if scale is None else scale,
                          values_in_keys=v_pool is None,
                          masked=keep is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(B,),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=buffers + [
                pltpu.SemaphoreType.DMA((len(pools), 2)),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, Wv), jnp.float32)]),
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 << 20),
        name="paged_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), pos,
      first.astype(jnp.int32), live.astype(jnp.int32),
      block_tables.reshape(-1).astype(jnp.int32), q, *pools,
      *(() if keep is None else (keep,)))
    return out if keep is None else (out[0], out[1].sum((1, 2)))
