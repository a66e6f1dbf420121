"""A sparse prefill chunk's two halves at toy sizes on the CPU: the
selection as a mask (`minicpm_sala.chosen_blocks`) against the set that
`select_blocks` / `lax.top_k` names, and `ops/paged_prefill_attention.py`,
the kernel that walks a row's pages a query block at a time, interpreted,
against a plain float32 softmax over the keys each token sees; what the
kernel never copies.  (The two together through the engine's programs:
tests/test_minicpm_sala.py, `across-dense_len`.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ray_tpu.models import minicpm_sala as ms
from ray_tpu.ops import paged_prefill_attention as ppa

# The cell's ratios at toy sizes: a page of 8 keys (64), 2 KV groups of 4
# heads (16), trips of 4 pages (8), a table of 22 pages (528).
PSZ, G, R, DH, NBLK, TRIP, LAYERS, LAYER = 8, 2, 4, 16, 22, 4, 3, 1
# ...and the selection's: 8 of up to 22 blocks (64 of 528), 1 + 3 forced
# (1 + 32), dense_len 8 blocks (128).
CFG = ms.SalaConfig(
    mixer_types=(ms.ATTN,), max_seq=PSZ * NBLK, vocab_size=64, d_model=32,
    n_heads=G * R, n_kv_heads=G, head_dim=DH, d_ff=64, lin_heads=2,
    lin_head_dim=8, block=PSZ, kernel=4, stride=2, init_blocks=1,
    local_blocks=3, topk=8, dense_len=PSZ * 8, dtype=jnp.float32)


# --------------------------------------------------------------- the mask

def _as_mask(ids, nb):
    mask = np.zeros(ids.shape[:-1] + (nb,), bool)
    np.put_along_axis(mask, np.asarray(ids), True, axis=-1)
    return mask


def _scored(qpos, seed=0, nb=NBLK, peaked=1.0):
    """(q, compressed keys, positions) of queries at `qpos`."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    q = peaked * jax.random.normal(keys[0], (len(qpos), G, R, DH))
    kc = jax.random.normal(keys[1], (1, G, nb * 4, DH))
    return q, kc, jnp.asarray(qpos, jnp.int32)


SELECTIONS = {
    # every token of a query block past dense_len, as the chunk scores them
    "a-query-block-past-dense_len": lambda: _scored(range(96, 104)),
    "the-tables-last-block": lambda: _scored(
        range(PSZ * NBLK - 8, PSZ * NBLK), seed=1),
    # 8 blocks at or before the query, all of them chosen
    "visible-blocks-are-exactly-topk": lambda: _scored([56, 59, 63], seed=2),
    # ...and fewer: top_k fills the set with blocks past the query (-1),
    # the lowest ids first, and so does the mask
    "fewer-visible-blocks-than-topk": lambda: _scored([17, 40], seed=3),
    # a softmax so peaked that most kernels' probabilities are exactly 0:
    # the set is filled from the zeros, the lowest ids first
    "scores-of-exactly-zero": lambda: _scored(
        range(160, 168), seed=4, peaked=400.0),
}


@pytest.mark.parametrize("case", list(SELECTIONS))
def test_the_mask_is_select_blocks_set(case):
    """Through the scorer both share: the chunk's mask marks exactly the
    blocks the tick's `select_blocks` lists, for every (token, group)."""
    q, kc, qpos = SELECTIONS[case]()
    score = ms.block_scores(q, kc, qpos, CFG)
    if case == "scores-of-exactly-zero":
        assert ((np.asarray(score) > 0).sum(-1) < CFG.topk).any()
    want = _as_mask(ms.select_blocks(q, kc, qpos, CFG), NBLK)
    got = np.asarray(jax.jit(ms.chosen_blocks, static_argnums=1)(
        score, CFG.topk))
    assert want.sum(-1).min() == got.sum(-1).max() == CFG.topk
    np.testing.assert_array_equal(got, want)
    own = np.asarray(qpos) // PSZ
    for n in range(len(own)):                     # forced, whatever else
        assert got[n, :, 0].all() and got[n, :, max(own[n] - 2, 0):own[n] + 1
                                          ].all()


def _ties_at_the_kth(rng):
    # four equals straddle the 8th place: two of them are in, the lower
    score = 0.4 * rng.random((6, G, NBLK)).astype(np.float32)
    score[..., [1, 2, 5, 16, 17, 21]] = 0.9
    score[..., [3, 9, 14, 20]] = 0.5
    return score, 8


def _all_equal(rng):
    return np.full((2, G, NBLK), 0.25, np.float32), 8


def _forced_fill_the_set(rng):
    score = rng.random((4, G, NBLK)).astype(np.float32)
    score[..., [0, 11, 12, 13]] = 1e9
    return score, 4


def _any_floats(rng):
    # the bisection orders every float32, not only what the scorer makes
    score = rng.normal(size=(8, G, NBLK)).astype(np.float32)
    score *= 10.0 ** rng.integers(-30, 30, score.shape)
    score[rng.random(score.shape) < 0.2] = 0.0
    score[rng.random(score.shape) < 0.1] = -1.0
    return score, 8


def _the_whole_row(rng):
    return rng.random((3, G, NBLK)).astype(np.float32), NBLK


@pytest.mark.parametrize("make", [_ties_at_the_kth, _all_equal,
                                  _forced_fill_the_set, _any_floats,
                                  _the_whole_row],
                         ids=lambda f: f.__name__.strip("_"))
def test_the_mask_is_top_ks_set_on_constructed_scores(make):
    """`lax.top_k`'s own rule where scores are equal (the lower index
    first), which `select_blocks` ends in."""
    score, k = make(np.random.default_rng(5))
    want = _as_mask(lax.top_k(jnp.asarray(score), k)[1], NBLK)
    got = np.asarray(ms.chosen_blocks(jnp.asarray(score), k))
    np.testing.assert_array_equal(got, want)
    if make is _ties_at_the_kth:
        assert got[..., [3, 9]].all() and not got[..., [14, 20]].any()


# ------------------------------------------------------------- the kernel

def _pools(seed=0, pages=NBLK + 9):
    rng = np.random.default_rng(seed)
    shape = (LAYERS, pages, G, PSZ, DH)
    return (rng.normal(size=shape).astype(np.float32),
            rng.normal(size=shape).astype(np.float32),
            1 + rng.permutation(pages - 1)[:NBLK].astype(np.int32), rng)


def _visible(rng, start_page, blocks, free=3, skip=(), per_group=False):
    """What each token of `blocks` query blocks from `start_page` sees:
    the first page, a window of three ending at its own page (that one
    up to itself), and `free` drawn pages before the window, none of
    them among `skip`."""
    T = blocks * PSZ
    vis = np.zeros((T, G, NBLK), np.int32)
    for t in range(T):
        own = start_page + t // PSZ
        early = [n for n in range(1, own - 2) if n not in skip]
        for g in range(G):
            if g == 0 or per_group:
                drawn = rng.choice(early, size=min(free, len(early)),
                                   replace=False)
            vis[t, g, drawn] = PSZ
        vis[t, :, 0] = PSZ
        vis[t, :, max(own - 2, 0):own] = PSZ
        vis[t, :, own] = t % PSZ + 1
    return vis


def _plain(q, k, v, bt, vis):
    """A float32 softmax over the keys each (token, head) sees."""
    T, H, _ = q.shape
    keys, vals = k[LAYER][bt], v[LAYER][bt]           # [NBLK, G, PSZ, DH]
    out = np.zeros((T, H, DH), np.float32)
    for t in range(T):
        for h in range(H):
            g = h // R
            seen = np.arange(PSZ)[None, :] < vis[t, g][:, None]
            s = (keys[:, g] @ q[t, h]) * DH ** -0.5
            e = np.where(seen, np.exp(s - s[seen].max()), 0.0)
            out[t, h] = np.einsum("np,npd->d", e / e.sum(), vals[:, g])
    return out


def _walk(q, k, v, bt, vis, dtype=jnp.float32):
    return np.asarray(jax.jit(
        lambda *a: ppa.query_block_attention(*a, interpret=True))(
        jnp.asarray(q, dtype), jnp.asarray(k, dtype), jnp.asarray(v, dtype),
        jnp.int32(LAYER), jnp.asarray(bt), jnp.asarray(vis)), np.float32)


@pytest.fixture(autouse=True)
def small_trips(monkeypatch):
    monkeypatch.setattr(ppa, "_TRIP_PAGES", TRIP)


def _whole_trips(rng):
    return 14, 2, _visible(rng, 14, 2)          # the block ends trip 3


def _context_not_whole_trips(rng):
    return 17, 2, _visible(rng, 17, 2)          # own pages mid-trip


def _the_tables_last_pages(rng):
    # 22 pages are five trips and a half: the last trip runs past the table
    return 20, 2, _visible(rng, 20, 2)


def _groups_choose_apart(rng):
    return 12, 3, _visible(rng, 12, 3, free=4, per_group=True)


def _own_page_alone(rng):
    # causal inside the block's own page, and nothing else but page 0
    vis = _visible(rng, 9, 1, free=0)
    vis[:, :, 7:9] = 0
    return 9, 1, vis


def _a_token_that_sees_nothing_in_the_first_trip(rng):
    vis = _visible(rng, 13, 1)
    vis[3, :, :TRIP] = 0                        # not even the first page
    vis[5, 1, :2 * TRIP] = 0
    return 13, 1, vis


CALLS = [_whole_trips, _context_not_whole_trips, _the_tables_last_pages,
         _groups_choose_apart, _own_page_alone,
         _a_token_that_sees_nothing_in_the_first_trip]


@pytest.mark.parametrize("make", CALLS, ids=lambda f: f.__name__.strip("_"))
def test_the_walk_is_a_plain_softmax_over_what_each_token_sees(make):
    k, v, bt, rng = _pools()
    start, blocks, vis = make(rng)
    q = 2.0 * rng.normal(size=(blocks * PSZ, G * R, DH)).astype(np.float32)
    # a page past the walk's last trip is never copied
    last = (start + blocks - 1) // TRIP
    k[:, bt[(last + 1) * TRIP:]] = np.nan
    got = _walk(q, k, v, bt, vis)
    np.testing.assert_allclose(got, _plain(q, k, v, bt, vis), atol=2e-6)


def test_a_trip_nobody_chose_is_not_walked():
    """No token of the block sees a page of trips 1 and 2: their pages
    hold NaN in both pools and the result does not."""
    k, v, bt, rng = _pools(seed=1)
    vis = _visible(rng, 18, 2, free=4, skip=range(TRIP, 3 * TRIP))
    assert not vis[:, :, TRIP:3 * TRIP].any() and vis[:, :, 3 * TRIP:].any()
    q = rng.normal(size=(2 * PSZ, G * R, DH)).astype(np.float32)
    want = _plain(q, k, v, bt, vis)
    k[:, bt[TRIP:3 * TRIP]] = v[:, bt[TRIP:3 * TRIP]] = np.nan
    np.testing.assert_allclose(_walk(q, k, v, bt, vis), want, atol=2e-6)


def test_the_walk_in_bfloat16_rounds_its_inputs_and_nothing_else():
    """bfloat16 pools and queries, as the cell serves them: the products'
    inputs are rounded, the softmax and the accumulator are float32."""
    k, v, bt, rng = _pools(seed=2)
    vis = _visible(rng, 15, 2)
    q = rng.normal(size=(2 * PSZ, G * R, DH)).astype(np.float32)
    rounded = [np.asarray(jnp.asarray(a, jnp.bfloat16), np.float32)
               for a in (q, k, v)]
    got = _walk(q, k, v, bt, vis, jnp.bfloat16)
    # (the weights are rounded to bfloat16 before they meet the values)
    np.testing.assert_allclose(got, _plain(*rounded, bt, vis), atol=2e-2)
    assert np.abs(got - _plain(q, k, v, bt, vis)).max() > 1e-3
