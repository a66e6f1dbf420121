"""`ops/paged_attention.py`, the ragged kernel of a decode tick's paged
attention layers, interpreted on the CPU: against the span loops of
`models/exaone_moe.py` and `models/deepseek_v2.py` (what runs where
there is no TPU) and against a plain float32 softmax over each row's own
keys, in both forms a model keeps a token's heads in and over a latent
page whose values are a prefix of its keys; what it reads of the pool
and what it never touches (an idle row's pages: all of them); the
latent form under a 0/1 mask a row (`keep`: a softmax over the kept
keys alone, the gather of `deepseek_v2._attend_chosen` on the same
choice, the count of keys weighed) and without one (the program it
always was); the counters that say what it copies; and, at the dense
body's shapes
(`models/decode.py`: pages of 16 tokens x 8 heads x 128, 16 or 32 query
heads, the block the chip runs), against the dense step's own span
loop."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import decode, llama
from ray_tpu.models import deepseek_v2 as ds
from ray_tpu.models import exaone_moe as em
from ray_tpu.models import glm_moe_dsa as gm
from ray_tpu.ops import paged_attention as pa

PAGE, NBLK, LAYERS, LAYER = 16, 12, 2, 1
BLOCK = 2 * PAGE                      # keys a block of the walk, here
# heads, key-value heads, key width, value width, heads side by side
KINDS = {"heads-in-rows": (8, 2, 128, 128, False),    # K-EXAONE's [G, 128]
         "heads-in-lanes": (8, 2, 192, 128, True),    # MiMo's G x 192, G x 128
         # DeepSeek-V2's latent row [512 | 64 | 64 zeros]: one head every
         # query head shares, its values its own first 512 lanes
         "latent": (8, 1, 640, 512, True),
         # the dense pool's [8, 128] under InternLM2's and Mistral's heads
         "dense-16": (16, 8, 128, 128, False),
         "dense-32": (32, 8, 128, 128, False)}
POOLS = ["heads-in-rows", "heads-in-lanes", "latent"]
# the latent step's own scale: of a 192-wide head, times YaRN's factor
LATENT = ds.DeepseekV2Config(max_seq=PAGE * NBLK, n_layers=5)
# positions of the call's rows (an idle row stands at 0 on the trash
# page, 0, whatever else the call holds, and reads nothing)
CASES = {
    "unequal-depths": [0, 17, 150, 40, 100],
    "mid-page": [PAGE * 3 + 5, 7],
    "last-block-of-the-table": [PAGE * NBLK - 1, 3],
    "idle-row-on-the-trash-page": [0, 0, 61],
    "one-row-alone": [77],
    "block-edges": [BLOCK - 1, BLOCK, 2 * BLOCK - 1, 2 * BLOCK],
}


def _state(kind, pos, seed=0, dtype=jnp.bfloat16, NBLK=NBLK):
    """(kind, q, k pool, v pool, block tables, positions): every row on
    pages of its own in a drawn order, an idle row's table on page 0.  A
    latent page has no value pool (None)."""
    H, G, Dh, Dv, flat = KINDS[kind]
    akind = em.AttnKind(G, Dh, Dv, flat=flat)
    rng = np.random.default_rng(seed)
    B = len(pos)
    P = B * NBLK + 1
    k = jnp.asarray(rng.normal(size=(LAYERS, P, PAGE) + em._kept(akind, Dh)),
                    dtype)
    v = None if kind == "latent" else jnp.asarray(
        rng.normal(size=(LAYERS, P, PAGE) + em._kept(akind, Dv)), dtype)
    q = jnp.asarray(rng.normal(size=(B, H, Dh)), dtype)
    bt = 1 + rng.permutation(B * NBLK).reshape(B, NBLK).astype(np.int32)
    pos = np.asarray(pos, np.int32)
    bt[pos == 0] = 0
    return kind, q, k, v, bt, pos


def _scale(kind):
    return LATENT.softmax_scale if kind == "latent" \
        else KINDS[kind][2] ** -0.5


def _plain(kind, q, k, v, bt, pos):
    """A float32 softmax over each row's own keys, row by row."""
    H, G, Dh, Dv, _ = KINDS[kind]
    out = []
    for b, p in enumerate(pos):
        if p == 0:                    # idle: nothing read, zeros
            out.append(np.zeros((H, Dv), np.float32))
            continue
        n = int(p) + 1
        pages = bt[b, :-(-n // PAGE)]
        keys = np.asarray(k[LAYER, pages], np.float32).reshape(-1, G, Dh)[:n]
        vals = keys[..., :Dv] if v is None else np.asarray(
            v[LAYER, pages], np.float32).reshape(-1, G, Dv)[:n]
        s = np.einsum("grd,sgd->grs", np.asarray(q[b], np.float32)
                      .reshape(G, H // G, Dh), keys) * _scale(kind)
        e = np.exp(s - s.max(-1, keepdims=True))
        out.append(np.einsum("grs,sgd->grd", e / e.sum(-1, keepdims=True),
                             vals).reshape(H, Dv))
    return np.stack(out)


def _kernel(kind, q, k, v, bt, pos):
    _, G, _, Dv, _ = KINDS[kind]
    how = dict(value_width=Dv, scale=_scale(kind)) if v is None else {}
    return np.asarray(jax.jit(
        lambda *a: pa.paged_attention(*a, n_kv_heads=G, interpret=True,
                                      **how))(
        q, k, v, jnp.int32(LAYER), jnp.asarray(bt), jnp.asarray(pos)),
        np.float32)


def _span(kind, q, k, v, bt, pos):
    """The model's own span loop: what runs where there is no TPU."""
    bt, pos = jnp.asarray(bt), jnp.asarray(pos)
    if v is None:
        cfg = dataclasses.replace(LATENT, dtype=q.dtype)
        return np.asarray(ds._span_tick(q, k, LAYER, bt, pos, cfg),
                          np.float32)
    _, G, Dh, Dv, flat = KINDS[kind]
    return np.asarray(em._span_tick(q, k, v, LAYER, bt, pos,
                                    em.AttnKind(G, Dh, Dv, flat=flat)),
                      np.float32)


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(pa, "_BLOCK_KEYS", BLOCK)
    monkeypatch.setattr(pa, "_BLOCK_BYTES", 0)
    monkeypatch.setattr(em, "_TICK_SPAN_KEYS", 3 * PAGE)
    monkeypatch.setattr(ds, "_TICK_SPAN_KEYS", 3 * PAGE)


def _holds(state, **kw):
    """The kernel's live rows are the span loop's and a plain softmax's
    (the span loop gives an idle row the trash page's first key); its
    idle rows are zeros."""
    got, pos = _kernel(*state), state[-1]
    np.testing.assert_allclose(got[pos > 0], _span(*state)[pos > 0], **kw)
    np.testing.assert_allclose(got, _plain(*state), **kw)
    np.testing.assert_array_equal(got[pos == 0], 0.0)


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("kind", POOLS)
def test_the_kernel_is_the_span_loop_and_a_plain_softmax(kind, case):
    """Rows of unequal depth, a depth that ends inside a page, a row at
    the table's last block, an idle row on the trash page, one row
    alone, depths on both sides of a block's edge: each live row's
    output is a softmax over ITS keys, in bfloat16 as the chip holds
    them and in float32; an idle row's is zeros."""
    # (bfloat16 weights into the weighted sum and a bfloat16 result:
    # one block's rounding is not another span's)
    _holds(_state(kind, CASES[case], seed=len(case)), atol=2e-2)
    exact = _state(kind, CASES[case], seed=len(case), dtype=jnp.float32)
    np.testing.assert_allclose(_kernel(*exact), _plain(*exact), atol=2e-5)


# The dense body's shapes, under the block the chip runs (384 keys = 24
# of its pages): rows that end exactly on, one short of and one past a
# block's edge; idle rows between live ones; a call of idle rows alone.
DENSE_NBLK = 50
DENSE_CASES = {
    "block-edges": [383, 384, 385, 767, 768],
    "idle-rows-between-live-ones": [200, 0, 385, 0, 0, 17],
    "every-row-idle": [0, 0, 0],
}


@pytest.mark.parametrize("case", list(DENSE_CASES))
@pytest.mark.parametrize("kind", ["dense-16", "dense-32"])
def test_the_kernel_at_the_dense_pools_shapes(kind, case, monkeypatch):
    """Pages of 16 tokens x 8 heads x 128 under 16 query heads (groups
    of 2, InternLM2's) and 32 (groups of 4, Mistral's), blocks of 24
    pages: live rows are the span loop's and a plain softmax's, idle
    rows finite zeros, and a call whose every row is idle starts no
    copy at all (a pool of not-a-number changes nothing)."""
    monkeypatch.undo()                # the real _BLOCK_KEYS, _BLOCK_BYTES
    assert pa.block_pages(PAGE, DENSE_NBLK, 8 * (128 + 128) * 2) == 24
    state = _state(kind, DENSE_CASES[case], seed=len(case), NBLK=DENSE_NBLK)
    _holds(state, atol=2e-2)
    _, q, k, v, bt, pos = state
    if not pos.any():
        np.testing.assert_array_equal(
            _kernel(kind, q, k.at[:].set(jnp.nan), v.at[:].set(jnp.nan),
                    bt, pos), 0.0)


def _dense_step(cfg, params, tok, pos, cache, bt, ragged, monkeypatch):
    """One `decode._dense_chunk_step` of a token a row, through the
    kernel (interpreted: `_on_tpu` says yes) or through the span loop."""
    with monkeypatch.context() as m:
        m.setattr(decode, "_on_tpu", lambda: ragged)
        m.setattr(pa, "paged_attention", functools.partial(
            pa.paged_attention, interpret=True))
        # (a function of its own a call: jax finds a function it has
        # traced again, whatever `_on_tpu` says by then)
        return jax.jit(lambda *a: decode._dense_chunk_step(*a, cfg))(
            params, tok[:, None], pos, cache, bt)


@pytest.mark.parametrize("heads", [16, 32])
def test_the_dense_tick_through_the_kernel_is_the_span_loops(heads,
                                                             monkeypatch):
    """The dense body's own step at its pool's shapes (one layer of 8 x
    128 key-value heads under `heads` query heads, pages of 16, the real
    block): a tick through the kernel gives the span loop's logits and
    leaves the same pool, with idle rows among the live ones."""
    monkeypatch.undo()
    cfg = llama.LlamaConfig(vocab_size=64, d_model=heads * 128,
                            n_heads=heads, n_kv_heads=8, n_layers=1,
                            d_ff=128, max_seq=PAGE * DENSE_NBLK,
                            dtype=jnp.bfloat16, remat=False,
                            use_flash=False)
    params = jax.tree_util.tree_map(
        lambda x: x.astype(cfg.dtype),
        llama.init_params(cfg, jax.random.PRNGKey(heads)))
    pos = np.asarray(DENSE_CASES["idle-rows-between-live-ones"], np.int32)
    rng = np.random.default_rng(heads)
    B = len(pos)
    bt = 1 + rng.permutation(B * DENSE_NBLK).reshape(B, DENSE_NBLK) \
        .astype(np.int32)
    bt[pos == 0] = 0
    fill = lambda: {  # noqa: E731
        name: jnp.asarray(np.random.default_rng(i).normal(
            size=(1, B * DENSE_NBLK + 1, PAGE, 8, 128)), cfg.dtype)
        for i, name in enumerate("kv")}
    tok = jnp.asarray(rng.integers(1, 64, size=B), jnp.int32)
    got, pool = _dense_step(cfg, params, tok, jnp.asarray(pos), fill(),
                            jnp.asarray(bt), True, monkeypatch)
    want, ref = _dense_step(cfg, params, tok, jnp.asarray(pos), fill(),
                            jnp.asarray(bt), False, monkeypatch)
    live = pos > 0
    got, want = np.asarray(got), np.asarray(want)
    # (two programs: an idle row attends to nothing in one and to the
    # trash page's first key in the other)
    assert (got[~live] != want[~live]).any()
    np.testing.assert_allclose(got[live], want[live], atol=3e-2)
    assert np.isfinite(got).all()
    for name in "kv":
        np.testing.assert_array_equal(
            np.asarray(pool[name][:, 1:], np.float32),
            np.asarray(ref[name][:, 1:], np.float32))


@pytest.mark.parametrize("kind", POOLS)
def test_a_row_reads_its_own_blocks_and_nothing_past_its_position(kind):
    """What the walk visits: not-a-number in every page past a row's
    last BLOCK (and in every page no table names, and in the trash page
    an idle row's table names) changes nothing, so none of it is copied;
    other keys in the row's own last block, past its position, are
    copied and masked; a key at or before its position is read."""
    state = _state(kind, CASES["unequal-depths"], seed=3)
    _, q, k, v, bt, pos = state
    want = _kernel(*state)
    blocks = (pos // BLOCK + 1) * (pos > 0)
    own = np.zeros(k.shape[1], bool)         # pages some row's walk visits
    for b in range(len(pos)):
        own[bt[b, :blocks[b] * BLOCK // PAGE]] = True
    poison = lambda a: a if a is None else (  # noqa: E731
        a.at[:, ~own].set(jnp.nan))
    np.testing.assert_array_equal(
        _kernel(kind, q, poison(k), poison(v), bt, pos), want)
    # past the position, inside the last block: masked, whatever is there
    row = 1                                   # position 17: page 1, slot 1
    page = int(bt[row, pos[row] // PAGE])
    after = (slice(None), page, slice(int(pos[row]) % PAGE + 1, None))
    np.testing.assert_array_equal(
        _kernel(kind, q, k.at[after].set(60.0),
                v if v is None else v.at[after].set(-60.0), bt, pos), want)
    # the position itself is read
    at = (LAYER, page, int(pos[row]) % PAGE)
    moved = _kernel(kind, q, *((k.at[at].add(4.0), v) if v is None else
                               (k, v.at[at].add(4.0))), bt, pos)
    assert np.abs(moved[row] - want[row]).max() > 1e-3
    np.testing.assert_array_equal(np.delete(moved, row, 0),
                                  np.delete(want, row, 0))
    # and only the layer asked for
    np.testing.assert_array_equal(
        _kernel(kind, q, k.at[0].set(jnp.nan),
                v if v is None else v.at[0].set(jnp.nan), bt, pos), want)


# --------------------------------------------- the latent form under a mask

S = PAGE * NBLK
K = 24                                # keys a row may choose, here


def _some(rng, pos, k=K):
    """`k` positions drawn among each row's 0..pos (all it holds where
    it holds no more)."""
    keep = np.zeros((len(pos), S), bool)
    for b, p in enumerate(pos):
        keep[b, rng.choice(p + 1, min(k, p + 1), replace=False)] = True
    return keep


def _in_pages(pos, pages):
    keep = np.zeros((len(pos), S), bool)
    for page in pages:
        keep[:, page * PAGE:(page + 1) * PAGE] = True
    return keep & (np.arange(S) <= np.asarray(pos)[:, None])


# positions of the call's rows, and the mask over each row's sequence
KEPT = {
    # (the row at 17 holds 18 keys, fewer than it may choose: all kept)
    "ragged-depths-an-idle-row-between": (
        [150, 0, 40, 191, 17], lambda rng, pos: _some(rng, pos)),
    "rows-that-hold-fewer-than-k": (
        [5, 23, 9], lambda rng, pos: _some(rng, pos)),
    # bits set past a row's position, on an idle row too: never weighed
    "bits-past-the-position": (
        [70, 0, 33, 120], lambda rng, pos: rng.random((len(pos), S)) < 0.3),
    # (page 3 is the second page of block 1: blocks 0 and 2.. keep none)
    "a-choice-confined-to-one-page": (
        [150, 100, 70], lambda rng, pos: _in_pages(pos, [3])),
    "one-key-a-page": (
        [150, 191, 37], lambda rng, pos: (np.arange(S) % PAGE == 5)
        & (np.arange(S) <= np.asarray(pos)[:, None])),
    "a-live-row-that-keeps-none": (
        [60, 90], lambda rng, pos: _some(rng, pos)
        & (np.arange(len(pos)) == 1)[:, None]),
}


def _kept_state(case, dtype):
    pos, choose = KEPT[case]
    state = _state("latent", pos, seed=len(case), dtype=dtype)
    keep = choose(np.random.default_rng(len(case)), state[-1])
    return state, np.asarray(keep, bool)


def _kept_kernel(state, keep):
    _, q, k, _, bt, pos = state
    out, n = jax.jit(lambda *a: pa.paged_attention(
        a[0], a[1], None, jnp.int32(LAYER), a[2], a[3], n_kv_heads=1,
        value_width=512, scale=_scale("latent"), keep=a[4],
        interpret=True))(q, k, jnp.asarray(bt), jnp.asarray(pos),
                         jnp.asarray(keep))
    return np.asarray(out, np.float32), np.asarray(n)


def _kept_plain(state, keep):
    """A float32 softmax over each row's kept keys at or before its
    position, row by row; zeros where there is none."""
    _, q, k, _, bt, pos = state
    out = []
    for b, p in enumerate(pos):
        at = np.flatnonzero(keep[b] & (np.arange(S) <= p) & (p > 0))
        if not len(at):
            out.append(np.zeros((q.shape[1], 512), np.float32))
            continue
        keys = np.asarray(k[LAYER, bt[b]], np.float32).reshape(S, -1)[at]
        s = np.asarray(q[b], np.float32) @ keys.T * _scale("latent")
        e = np.exp(s - s.max(-1, keepdims=True))
        out.append(e / e.sum(-1, keepdims=True) @ keys[:, :512])
    return np.stack(out)


def _kept_gather(state, keep):
    """`deepseek_v2._attend_chosen` on the same choice, listed as the
    selecting model lists it."""
    _, q, k, _, bt, pos = state
    live = pos > 0
    seen = jnp.asarray(keep & (np.arange(S) <= pos[:, None]) & live[:, None])
    slots = max(int(seen.sum(-1).max()), 1)
    order = np.concatenate([np.flatnonzero(live), np.flatnonzero(~live)])
    chosen = ds.Chosen(seen, lambda: gm._listed_by_blocks(seen, slots), True,
                       jnp.int32(live.sum()), jnp.asarray(order, jnp.int32))
    cfg = dataclasses.replace(LATENT, dtype=q.dtype)
    out, took = jax.jit(lambda q, k, bt: ds._attend_chosen(
        q, k, LAYER, bt, chosen, cfg))(q, k, jnp.asarray(bt))
    return np.asarray(out, np.float32), int(took)


@pytest.mark.parametrize("case", list(KEPT))
def test_under_a_mask_the_walk_is_a_softmax_over_the_kept_keys(case):
    """The latent form under `keep`: each live row's output is a softmax
    over the kept keys at or before its position and over nothing else
    (a float32 reference; the gather `deepseek_v2._attend_chosen` makes
    of the same choice), in float32 and in bfloat16 as the chip holds
    the pool; a bit past the position, or on an idle row, weighs
    nothing; a block that keeps no key (a row's first ones too) and a
    row that keeps none at all leave finite numbers (zeros for the
    row); and the kernel's count of the keys it weighed, row by row, is
    the mask's."""
    state, keep = _kept_state(case, jnp.float32)
    pos = state[-1]
    got, n = _kept_kernel(state, keep)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, _kept_plain(state, keep), atol=2e-5)
    weighed = (keep & (np.arange(S) <= pos[:, None])
               & (pos > 0)[:, None]).sum(-1)
    np.testing.assert_array_equal(n, weighed)
    none = weighed == 0
    # (a live row with no slot filled, which no choice makes: the
    # gather's softmax over nothing is not a number)
    gathered, took = _kept_gather(state, keep)
    np.testing.assert_allclose(got[~none], gathered[~none], atol=2e-5)
    assert took == weighed.sum()
    np.testing.assert_array_equal(got[none], 0.0)
    assert (np.abs(got[~none]).max(axis=(1, 2)) > 0).all()
    state, keep = _kept_state(case, jnp.bfloat16)
    got, n = _kept_kernel(state, keep)
    np.testing.assert_allclose(got, _kept_plain(state, keep), atol=2e-2)
    np.testing.assert_array_equal(n, weighed)


def test_a_mask_of_every_key_is_the_walk_without_one():
    """`keep` all ones weighs what the unmasked walk weighs: the same
    output, and a count of `pos + 1` a live row."""
    state = _state("latent", CASES["unequal-depths"], seed=2)
    pos = state[-1]
    got, n = _kept_kernel(state, np.ones((len(pos), S), bool))
    np.testing.assert_allclose(got, _kernel(*state), atol=2e-2)
    np.testing.assert_array_equal(n, (pos + 1) * (pos > 0))


def _calls(jaxpr):
    """The `pallas_call` equations of a jaxpr, wherever they nest."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _calls(sub)


def _equations(jaxpr):
    """The equations of a jaxpr, those of its loops and branches too."""
    return sum(1 + sum(map(_equations, jax.core.jaxprs_in_params(e.params)))
               for e in jaxpr.eqns)


# the equations of the kernel's body at this file's shapes as the parent
# commit of PR 67 (fb0acd1) traced them
PARENT_BODY = {"heads-in-rows": 220, "heads-in-lanes": 186, "latent": 172}


@pytest.mark.parametrize("kind", POOLS)
def test_without_a_mask_the_call_is_the_program_it_was(kind):
    """`keep=None` is a static branch: the call traces to the parent's
    program in all three forms, held here by what PR 67's comparison of
    whole jaxprs and Mosaic modules read off the parent commit (the
    kernel's operands, its one output, the equations of its body), and
    a mask adds to that: an operand, an output, equations."""
    _, q, k, v, bt, pos = _state(kind, CASES["unequal-depths"])
    _, G, _, Dv, _ = KINDS[kind]
    how = dict(value_width=Dv, scale=_scale(kind)) if v is None else {}

    def traced(**kw):
        jaxpr = jax.make_jaxpr(lambda *a: pa.paged_attention(
            *a, n_kv_heads=G, interpret=True, **how, **kw))(
            q, k, v, jnp.int32(LAYER), jnp.asarray(bt), jnp.asarray(pos))
        call, = _calls(jaxpr.jaxpr)
        return (len(call.invars), len(call.outvars),
                _equations(call.params["jaxpr"]))

    pools = 1 if v is None else 2
    operands, outputs, body = traced()
    # five prefetched scalars, the query, the pools: and one output
    assert (operands, outputs) == (6 + pools, 1)
    assert body == PARENT_BODY[kind]
    if v is None:
        keep = jnp.ones((len(pos), S), bool)
        assert traced(keep=keep)[:2] == (8, 2)
        assert traced(keep=keep)[2] > body
    else:
        with pytest.raises(ValueError, match="keys that hold their values"):
            traced(keep=jnp.ones((len(pos), S), bool))


@pytest.mark.parametrize("case", list(CASES))
def test_the_counters_are_what_the_kernel_copies(case, monkeypatch):
    """`attn_keys_paged` and `attn_keys_gathered` on a TPU: each row's
    own blocks, the last one whole, in every paged layer: the pages the
    walk of the test above visits for the same positions (of an idle
    row none); without one, the span loop's spans to the deepest row for
    every row."""
    cfg = em.ExaoneMoeConfig(max_seq=PAGE * NBLK, n_layers=8)
    pos = np.asarray(CASES[case], np.int32)
    active = pos[pos > 0]
    held = int((active + 1).sum()) * cfg.n_global
    cols = 3 * PAGE
    spans = len(pos) * -(-(int(pos.max()) + 1) // cols) * cols
    assert em.attn_keys_paged(cfg, active, pos, PAGE, NBLK) == (
        spans * cfg.n_global, held)
    monkeypatch.setattr(em, "_on_tpu", lambda: True)
    visited = sum(len(range(0, int(p) + 1, BLOCK)) for p in active) * BLOCK
    assert pa.keys_copied(pos, PAGE, NBLK, 4096) == visited
    assert em.attn_keys_paged(cfg, active, pos, PAGE, NBLK) == (
        visited * cfg.n_global, held)
    assert em.attn_keys_gathered(cfg, pos, PAGE, NBLK) == (
        visited * cfg.n_global + len(pos) * cfg.window * cfg.n_window)
    # what is pulled for each key held: under one block a row over 1
    assert visited - int((pos + 1).sum()) <= len(pos) * BLOCK


@pytest.mark.parametrize("case", list(CASES))
def test_the_latent_counter_is_what_the_kernel_copies(case, monkeypatch):
    """`deepseek_v2.attn_keys_gathered` on a TPU: each row's own blocks
    in every layer, what the kernel copies; without one, the span loop's
    spans to the deepest row for every row."""
    pos = np.asarray(CASES[case], np.int32)
    cols = 3 * PAGE
    spans = len(pos) * -(-(int(pos.max()) + 1) // cols) * cols
    assert ds.attn_keys_gathered(LATENT, pos, PAGE, NBLK) == (
        spans * LATENT.n_layers)
    monkeypatch.setattr(ds, "_on_tpu", lambda: True)
    visited = sum(len(range(0, int(p) + 1, BLOCK))
                  for p in pos[pos > 0]) * BLOCK
    assert ds.attn_keys_gathered(LATENT, pos, PAGE, NBLK) == (
        pa.keys_copied(pos, PAGE, NBLK, 1280) * LATENT.n_layers) == (
        visited * LATENT.n_layers)


@pytest.mark.parametrize("case", list(CASES))
def test_the_dense_counter_is_what_the_program_copies(case, monkeypatch):
    """`decode.DENSE_BODY.keys_gathered`: a tick on a TPU counts each
    live row's own blocks in every layer (an idle row's: none), what
    the kernel copies; the verify (t tokens a row), heads narrower than
    the chip's lanes and every other backend count the span loop's
    spans to the deepest row for every row."""
    cfg = llama.LlamaConfig(vocab_size=64, d_model=256, n_heads=2,
                            n_kv_heads=1, n_layers=3, d_ff=64,
                            max_seq=PAGE * NBLK, dtype=jnp.bfloat16)
    narrow = dataclasses.replace(cfg, n_heads=4, n_kv_heads=2)
    pos = np.asarray(CASES[case], np.int32)
    rows, L = len(pos), cfg.n_layers
    def gathered(c, last, t=1):
        return decode.DENSE_BODY.keys_gathered(c, -1, last, PAGE, NBLK, t)

    def spans(c, last):
        cols = PAGE * decode.paged_span_blocks(
            rows * PAGE * c.n_kv_heads * c.head_dim * 2, PAGE, NBLK)
        return rows * L * -(-(int(last.max()) + 1) // cols) * cols

    assert gathered(cfg, pos) == spans(cfg, pos)
    monkeypatch.setattr(decode, "_on_tpu", lambda: True)
    visited = sum(len(range(0, int(p) + 1, BLOCK))
                  for p in pos[pos > 0]) * BLOCK
    assert gathered(cfg, pos) == visited * L \
        == pa.keys_copied(pos, PAGE, NBLK, 2 * 128 * 2) * L
    assert gathered(cfg, pos + 3, t=4) == spans(cfg, pos + 3)
    assert gathered(narrow, pos) == spans(narrow, pos)


def test_keys_that_hold_their_values_are_one_head():
    """No value pool means a value width inside a key of ONE head: a
    pool of several heads, or a width past the key's, is refused by
    name, before anything is traced into a kernel."""
    _, q, k, _, bt, pos = _state("latent", [5])
    call = lambda **kw: pa.paged_attention(  # noqa: E731
        q, k, None, jnp.int32(LAYER), jnp.asarray(bt), jnp.asarray(pos),
        interpret=True, **kw)
    for kw in (dict(n_kv_heads=2, value_width=512),
               dict(n_kv_heads=1, value_width=641),
               dict(n_kv_heads=1)):
        with pytest.raises(ValueError, match="keys that hold their values"):
            call(**kw)


def test_a_block_is_whole_pages_of_the_table():
    assert pa.block_pages(PAGE, NBLK, 4096) == BLOCK // PAGE
    assert pa.block_pages(PAGE // 2, 432, 4096) == 2 * BLOCK // PAGE
    assert pa.block_pages(4 * BLOCK, NBLK, 4096) == 1  # a page over a block
    assert pa.block_pages(1, 5, 4096) == 5             # a table under one


@pytest.mark.parametrize("token_bytes,keys", [
    (8 * (128 + 128) * 2, 384),       # K-EXAONE's [8, 128] keys and values
    (4 * (192 + 128) * 2, 384),       # MiMo's 4 x 192 and 4 x 128
    (640 * 2, 768),                   # DeepSeek-V2's latent row
    (8 * (128 + 128) * 4, 384)])      # float32: never under 384 keys
def test_a_thin_token_makes_a_block_of_more_keys(token_bytes, keys,
                                                 monkeypatch):
    """The block as the chip runs it (the constants themselves, not the
    small blocks of this file): 384 keys of the two pools PR 52 measured
    it on, whose programs stay what they were, and as many 1.25 KB
    latents as fill what 384 of MiMo's tokens do."""
    monkeypatch.undo()                # the real _BLOCK_KEYS, _BLOCK_BYTES
    assert pa.block_pages(64, 432, token_bytes) * 64 == keys
    assert pa.keys_copied([0, 767, 768], 64, 432, token_bytes) == (
        {384: 0 + 2 + 3, 768: 0 + 1 + 2}[keys] * keys)
