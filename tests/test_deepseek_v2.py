"""DeepSeek-V2 at toy widths on the CPU, seeded weights: the engine's own
two programs (expanded chunks, absorbed ticks) against one forward of the
plain reference, the routing against hand cases, the expert layer's
shares against the uncut layer, the controls a comparison must catch,
the benchmark's architecture files against the program, the guards for
everything that frames a page as K then V, and the toy configuration
served to `correct` from a temporary benchmark root."""

import ast
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import decode
from ray_tpu.models import deepseek_v2 as ds
from ray_tpu.models import gpt
from ray_tpu.serve.llm import engine as engine_mod
from ray_tpu.serve.llm import kv_transfer
from ray_tpu.serve.llm.engine import GenerationEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
ARCH_DIR = os.path.join(BENCH, "archs", "deepseek_v2")
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# The toy configuration, as a benchmark file would state it: 16 routed
# experts in 4 groups, 2 groups kept, top-3; experts 4..7 are held here
# (the second of four shares); a dense layer and two expert layers.
C = {
    "name": "toy-dsv2", "arch": "deepseek_v2", "attention_bias": False,
    "first_k_dense_replace": 1, "hidden_size": 32, "intermediate_size": 64,
    "kv_lora_rank": 16, "moe_intermediate_size": 16, "moe_layer_freq": 1,
    "n_group": 4, "n_routed_experts": 4, "expert_offset": 4,
    "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "num_experts_per_tok": 3, "num_hidden_layers": 3, "q_lora_rank": 24,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 16, "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 16,
    "scoring_func": "softmax", "topk_group": 2,
    "topk_method": "group_limited_greedy", "vocab_size": 128,
    "torch_dtype": "float32",
    "published": {"n_routed_experts": 16, "num_hidden_layers": 6,
                  "vocab_size": 512},
    "serving": {"engine": {"num_slots": 3, "max_seq": 128, "page_size": 8,
                           "kv_pages": 64, "prefill_chunk": 16},
                "check": {"prompt_len": 48, "decode_tokens": 4,
                          "tolerance": {"max_abs_diff": 1e-3,
                                        "mean_abs_diff": 1e-4}}}}
PSZ, CHUNK, NBLK, ROWS = 8, 16, 16, 3
K, N_MOE = C["num_experts_per_tok"], 2


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "dsv2_" + name, os.path.join(ARCH_DIR, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def arch():
    from benchmarks.lib.registry import arch_of
    return arch_of(C, BENCH)


@pytest.fixture(scope="module")
def reference():
    return _load("reference")


@pytest.fixture(scope="module")
def model(arch):
    cfg = arch.build(C, C["serving"]["engine"]["max_seq"], remat=False)
    params = arch.init(cfg, jax.random.PRNGKey(7), jnp.float32)
    # norms that are not all ones, so a missing one shows
    bump = iter(jax.random.split(jax.random.PRNGKey(8), 64))
    params = jax.tree_util.tree_map(
        lambda w: w if w.ndim != 1 else
        w + 0.1 * jax.random.normal(next(bump), w.shape), params)
    return cfg, params


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        1, C["vocab_size"], size=n).astype(np.int32)


def _prefill(params, cfg, cache, bt_row, toks, start=0):
    """`toks` through engine._prefill_chunk chunk by chunk from column
    `start`, as the engine's admission does."""
    rows = []
    for s in range(0, len(toks), CHUNK):
        real = toks[s:s + CHUNK]
        chunk = np.zeros((1, CHUNK), np.int32)
        chunk[0, :len(real)] = real
        logits, cache = engine_mod._prefill_chunk(
            params, jnp.asarray(chunk), jnp.int32(start + s), cache,
            jnp.asarray(bt_row[None]), cfg, slot=jnp.int32(0),
            valid=jnp.int32(len(real)))
        rows.append(np.asarray(logits[0, :len(real)]))
    return np.concatenate(rows), cache


def _tick(params, cfg, cache, bt, pos, tok):
    _, logits, cache = engine_mod._paged_tick(
        params, jnp.asarray(tok), jnp.asarray(pos), cache, jnp.asarray(bt),
        cfg, with_logits=True)
    logits = np.asarray(logits)
    assert np.isfinite(logits).all()       # idle rows too
    return logits, cache


def _fresh(cfg):
    return decode.init_paged_cache(cfg, 49, PSZ, ROWS)


# ------------------------------------ the engine's programs = one forward

def _through_the_programs(case, cfg, params):
    """(logits from the engine's two programs, the tokens they belong
    to, the cache after) for one sequence, driven as `case` says."""
    cache = _fresh(cfg)
    bt = np.zeros((ROWS, NBLK), np.int32)
    pos = np.zeros((ROWS,), np.int32)
    tok = np.zeros((ROWS,), np.int32)
    n_prompt, n_decode, slot = {"whole-chunks": (32, 5, 0),
                                "partial-last-chunk": (77, 6, 1),
                                "two-rows": (45, 6, 1)}[case]
    toks = _tokens(n_prompt + n_decode, seed=len(case))
    if case == "two-rows":
        # another row decodes at another depth all the while
        other = _tokens(70, seed=5)
        row = np.zeros((NBLK,), np.int32)
        row[:10] = np.arange(20, 30)
        _, cache = _prefill(params, cfg, cache, row, other)
        bt[2], pos[2], tok[2] = row, 70, 9
    row = np.zeros((NBLK,), np.int32)
    row[:12] = np.arange(3, 15)
    got, cache = _prefill(params, cfg, cache, row, toks[:n_prompt])
    bt[slot] = row
    rows = [got]
    for i in range(n_decode):
        pos[slot], tok[slot] = n_prompt + i, toks[n_prompt + i]
        logits, cache = _tick(params, cfg, cache, bt, pos, tok)
        rows.append(logits[slot][None])
        if case == "two-rows":
            pos[2] += 1
    return np.concatenate(rows), toks, cache


@pytest.mark.parametrize("case", ["whole-chunks", "partial-last-chunk",
                                  "two-rows"])
def test_prefill_chunks_then_ticks_are_one_reference_forward(
        model, reference, case, tick_attention):
    """Chunks of 16 through the EXPANDED attention, then ticks through
    the ABSORBED one (the span loop, and the ragged kernel as a TPU runs
    it), over one cache: every position's logits against the
    reference's expanded forward — so absorbed = expanded, a chunk
    reads what earlier chunks cached, a
    padded last chunk routes no pad, and a second row changes nothing."""
    cfg, params = model
    got, toks, cache = _through_the_programs(case, cfg, params)
    want, routes = reference.forward(params, jnp.asarray(toks), C,
                                     query_block=16, width_blocks=2,
                                     with_routes=True)
    assert np.asarray(want).std() > 0.05
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-5)
    if case != "two-rows":
        # the program's own counters are the reference's routing
        routes = np.asarray(routes)
        counts = ds.read_counters(cache, cfg)
        assert counts["pairs_routed"] == routes.size == len(toks) * K * N_MOE
        assert counts["pairs_local"] == int(
            ((routes >= 4) & (routes < 8)).sum())
        n_ticks = len(toks) - {"whole-chunks": 32,
                               "partial-last-chunk": 77}[case]
        assert counts["experts_held"] == n_ticks * N_MOE * 4
        assert 0 < counts["experts_touched"] <= counts["experts_held"]
        assert counts["load_max"] >= counts["pairs_local"] / 4 / N_MOE


@pytest.mark.parametrize("keys", [16, 24])
def test_attention_in_several_spans_is_the_same_attention(
        model, reference, monkeypatch, keys):
    """Spans of 2 and of 3 pages instead of the whole toy table (the
    real sizes walk 120 pages in spans of 4 and 8): the softmax parts
    merged by maxima and sums are the softmax; 3 pages do not divide the
    table's 16, so its last span is clamped back over the one before."""
    cfg, params = model
    monkeypatch.setattr(ds, "_TICK_SPAN_KEYS", keys)
    monkeypatch.setattr(ds, "_CHUNK_SPAN_KEYS", keys)
    programs = (engine_mod._prefill_chunk, engine_mod._paged_tick)
    for program in programs:
        program.clear_cache()
    try:
        got, toks, _ = _through_the_programs("partial-last-chunk", cfg,
                                             params)
    finally:
        for program in programs:
            program.clear_cache()
    want = reference.forward(params, jnp.asarray(toks), C, query_block=16)
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-5)


def test_yarn_frequencies_are_the_references(model, reference):
    cfg, _ = model
    np.testing.assert_allclose(ds.yarn_inv_freq(cfg),
                               np.asarray(reference.yarn_inv_freq(C)),
                               rtol=1e-6)
    assert cfg.softmax_scale == pytest.approx(reference.softmax_scale(C))
    # the published sizes: 192^-0.5 x (0.1 x 0.707 x ln 40 + 1)^2
    real = ds.DeepseekV2Config(max_seq=8)
    assert real.softmax_scale == pytest.approx(
        192 ** -0.5 * (0.1 * 0.707 * np.log(40) + 1) ** 2)
    f = ds.yarn_inv_freq(real)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    assert f[0] == pytest.approx(plain[0]) \
        and f[-1] == pytest.approx(plain[-1] / 40)


# ------------------------------------------------------------- the routing

def _route_by_hand(logits):
    """ids and weights of `ds.route` for tokens whose router logits are
    `logits` [N, 16]: the router is those rows, the tokens one-hot."""
    cfg = ds.DeepseekV2Config(
        max_seq=8, d_model=len(logits), n_routed_experts=16, n_group=4,
        topk_group=2, top_k=3, routed_scaling_factor=16.0)
    router = jnp.asarray(logits, jnp.float32)
    ids, w = ds.route(router, jnp.eye(len(logits)), cfg)
    return np.asarray(ids), np.asarray(w)


def test_routing_against_a_hand_case():
    """Experts 0-3 / 4-7 / 8-11 / 12-15 are the groups.  Token 0: the
    best experts are 0 (group 0), 5 (group 1), then 8 and 9 (group 2);
    groups 0 and 1 are kept, so 8 and 9 are not eligible and the third
    choice is 1.  Token 1: every expert of group 3 beats all others but
    one; top-3 takes three of them."""
    logits = np.full((2, 16), -2.0)
    logits[0, [0, 5, 8, 9, 1]] = [3.0, 2.5, 2.0, 1.9, 0.5]
    logits[1, [12, 13, 14, 15, 2]] = [1.0, 1.2, 1.4, 1.6, 2.0]
    ids, w = _route_by_hand(logits)
    assert sorted(ids[0]) == [0, 1, 5]            # the group limit
    assert sorted(ids[1]) == [2, 14, 15]          # top-3, in 2 groups
    p = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    for n in range(2):
        # the scores themselves x 16: not renormalised over the three
        np.testing.assert_allclose(w[n], 16 * p[n, ids[n]], rtol=1e-5)
        assert abs(w[n].sum() - 16) > 1


def test_no_token_is_dropped_when_one_expert_takes_every_token(
        model, reference):
    """Every token is the same row, so all N x top-3 pairs go to the
    same three experts, two of them held here: the grouped product is
    sized for that and each token gets its full sum."""
    cfg, params = model
    lp = params["layers"][1]
    h = jnp.tile(jax.random.normal(jax.random.PRNGKey(3), (1, 32)), (40, 1))
    ids, w = ds.route(lp["router"], h, cfg)
    # choose the share that holds the first two choices of that row
    first = int(np.asarray(ids)[0, 0]) // 4 * 4
    cfg2 = ds.DeepseekV2Config(**{**cfg.__dict__, "expert_offset": first})
    out, sizes = ds.routed_experts(lp["experts"], h, ids, w,
                                   jnp.ones((40,), bool), cfg2)
    held = [e - first for e in np.asarray(ids)[0] if first <= e < first + 4]
    assert sorted(np.asarray(sizes)) == sorted([40] * len(held)
                                               + [0] * (4 - len(held)))
    with jax.default_matmul_precision("highest"):
        want = reference.moe(h, lp, dict(C, expert_offset=first,
                                         _no_shared=True))
    assert float(jnp.abs(want).max()) > 1e-3
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)
    assert np.abs(np.asarray(out) - np.asarray(out)[0]).max() == 0


def _experts_by_hand(ex, h, w, ids, live, held, offset=0):
    """(the held experts' part of the layer [n_tok, D], tokens on each
    held expert) by a masked loop over tokens in float32."""
    want = np.zeros(h.shape, np.float32)
    on = np.zeros((held,), np.int64)
    h32, w32 = np.asarray(h, np.float32), np.asarray(w, np.float32)
    gate, up, down = (np.asarray(ex[n], np.float32)
                      for n in ("w_gate", "w_up", "w_down"))
    for t in range(len(h32)):
        for j in range(ids.shape[1]):
            e = ids[t, j] - offset
            if live[t] and 0 <= e < held:
                a = h32[t] @ gate[e]
                want[t] += w32[t, j] * (
                    (a / (1 + np.exp(-a)) * (h32[t] @ up[e])) @ down[e])
                on[e] += 1
    return want, on


def _held_pairs_case(case, n_tok, k, slab):
    """(expert ids [n_tok, k] with experts 4..7 held, live [n_tok]) for
    one case of the slab walk; ids are set by hand (a router would not
    repeat an expert in a token, the layer does not care)."""
    ids = np.full((n_tok, k), 9, np.int32)            # 9: held elsewhere
    live = np.ones((n_tok,), bool)
    flat = ids.reshape(-1)
    spread = lambda n: np.random.default_rng(n).permutation(  # noqa: E731
        n_tok * k)[:n]
    if case == "none held":
        pass
    elif case == "a few held: one slab":
        flat[spread(5)] = [4, 7, 7, 5, 4]
    elif case == "exactly a slab":
        flat[spread(slab)] = 4 + np.arange(slab) % 4
    elif case == "a slab and one pair":
        flat[spread(slab + 1)] = 4 + np.arange(slab + 1) % 3
    elif case == "every pair on one held expert":
        flat[:] = 6
    elif case == "dead rows and a chunk's pad":
        flat[:] = 4 + np.arange(n_tok * k) % 4        # all held ...
        live[[0, 3, 4]] = False                       # ... idle rows
        live[n_tok - 5:] = False                      # ... and the pad
    return ids, live


@pytest.mark.parametrize("tile", [8, 128])
@pytest.mark.parametrize("case", [
    "none held", "a few held: one slab", "exactly a slab",
    "a slab and one pair", "every pair on one held expert",
    "dead rows and a chunk's pad"])
def test_the_experts_walk_the_held_pairs_in_slabs(model, monkeypatch, case,
                                                  tile):
    """`routed_experts` against a plain loop over tokens in float32,
    with the grouped matmul's row tile cut to 8 so that 16 tokens x
    top-3 make slabs of 16 rows of 48 pairs: the trips follow the pairs
    held (none, one, one full, two, all three), nothing is dropped, a
    dead row or a pad counts nowhere, and `pairs_worked` is trips x
    slab.  At the tile as it stands the 48 pairs fit one slab, which
    runs once whatever is held (no loop)."""
    cfg, params = model
    monkeypatch.setattr(ds, "_GMM_ROWS", tile)
    n_tok, k = 16, cfg.top_k
    tm, slab, most = ds._slab(n_tok, k, cfg)
    assert (tm, slab, most) == ((8, 16, 3) if tile == 8 else (48, 48, 1))
    # weights large enough that a missing or doubled pair shows
    ex = jax.tree_util.tree_map(lambda a: 16 * a,
                                params["layers"][1]["experts"])
    h = jax.random.normal(jax.random.PRNGKey(11), (n_tok, 32))
    w = jax.random.uniform(jax.random.PRNGKey(12), (n_tok, k),
                           minval=0.5, maxval=2.0)
    ids, live = _held_pairs_case(case, n_tok, k, 16)

    out, sizes = jax.jit(
        lambda *a: ds.routed_experts(ex, *a, cfg))(
            h, jnp.asarray(ids), w, jnp.asarray(live))

    want, on = _experts_by_hand(ex, h, w, ids, live, 4, cfg.expert_offset)
    np.testing.assert_array_equal(np.asarray(sizes), on)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4, atol=1e-4)
    assert np.abs(np.asarray(out)[~live]).max(initial=0) == 0
    if on.sum():
        assert np.abs(want).max() > 1
    counted = dict(zip(ds.COUNTERS, ds.count_routed(
        [jnp.int32(0)] * len(ds.COUNTERS), jnp.asarray(live), sizes, False,
        cfg)))
    trips = {"none held": 0, "a few held: one slab": 1, "exactly a slab": 1,
             "a slab and one pair": 2, "every pair on one held expert": 3,
             "dead rows and a chunk's pad": 2}[case]
    assert -(-int(on.sum()) // 16) == trips
    assert int(counted["pairs_worked"]) == (trips * 16 if tile == 8 else 48)
    assert int(counted["pairs_local"]) == on.sum()
    assert int(counted["pairs_routed"]) == live.sum() * k
    assert int(counted["expert_reads"]) == 0        # counted in ticks only
    ticked = dict(zip(ds.COUNTERS, ds.count_routed(
        [jnp.int32(0)] * len(ds.COUNTERS), jnp.asarray(live), sizes, True,
        cfg)))
    assert int(ticked["expert_reads"]) == _reads_by_hand(on, tm, slab, 1)
    assert int(ticked["experts_touched"]) == (on > 0).sum()


def _reads_by_hand(sizes, tm, slab, tiles_k):
    """An expert's weight block's fetches in one projection's walk, as
    the grouped matmul's schedule goes, on the host: slabs of `slab`
    rows, in each the (expert, row tile) visits in order of expert; a
    visit that follows one of the same expert re-uses the block it
    holds where the block is the whole contraction (`tiles_k` 1)."""
    ends = np.cumsum(sizes)
    starts, n = ends - sizes, 0
    for lo in range(0, int(ends[-1]), slab):
        last = None
        for g in range(len(sizes)):
            a, b = max(starts[g], lo), min(ends[g], lo + slab)
            for _ in range(a // tm, (b - 1) // tm + 1) if b > a else ():
                n += tiles_k > 1 or last != g
                last = g
    return n


def _walk_case(case, n_tok, k, n_all):
    """(expert ids [n_tok, k] among `n_all`, live [n_tok]) by hand."""
    rng = np.random.default_rng(len(case))
    ids = np.stack([rng.permutation(n_all)[:k] for _ in range(n_tok)]
                   ).astype(np.int32)
    live = np.ones((n_tok,), bool)
    if case == "every pair on one expert":
        ids[:] = 5
    elif case == "dead rows and a chunk's pad":
        live[[0, 3, 4]] = False
        live[n_tok - 5:] = False
    elif case == "a group across three row tiles":
        ids[:, 0] = 2                 # n_tok rows of expert 2, tile 16
    return ids, live


@pytest.mark.parametrize("held,d_model,budget", [
    (32, 32, None), (8, 32, None), (32, 256, 1 << 16), (8, 256, 1 << 16)],
    ids=["all held: one slab", "a share held: the walk",
         "all held, the contraction in two tiles",
         "a share held, the contraction in two tiles"])
@pytest.mark.parametrize("case", [
    "top-8 over many experts", "every pair on one expert",
    "dead rows and a chunk's pad", "a group across three row tiles"])
def test_the_walk_reads_a_held_expert_once_a_slab(monkeypatch, case, held,
                                                  d_model, budget):
    """`routed_experts` against the masked loop over tokens in float32
    at top-8 of 32 experts, 40 tokens, a row tile of 16: where all 32
    are held the 320 pairs are ONE slab of 20 tiles in line (groups of
    ~10 rows lie across tiles; the sum back goes by the sort's
    inverse), where 8 are held they are walked in slabs of 48 rows.
    `expert_reads` is the kernel's schedule walked by hand: where a grid
    step holds the whole contraction an expert is fetched once a slab it
    has rows in (once a call in one slab), where it does not (the byte
    budget cut to 128 of 256 rows) every visit fetches it again;
    `pairs_worked` is trips x slab."""
    monkeypatch.setattr(ds, "_GMM_ROWS", 16)
    if budget:
        monkeypatch.setattr(ds, "_GMM_TILE_BYTES", budget)
    n_tok, k, n_all, F = 40, 8, 32, 16
    cfg = ds.DeepseekV2Config(
        max_seq=8, d_model=d_model, moe_d_ff=F, n_routed_experts=n_all,
        n_group=1, topk_group=1, top_k=k, experts_held=held,
        expert_offset=0, dtype=jnp.float32)
    tiles_k = d_model // ds._tiles(d_model, F, 4)[0]
    assert tiles_k == (2 if budget else 1)
    tm, slab, most = ds._slab(n_tok, k, cfg)
    assert (tm, slab, most) == ((16, 320, 1) if held == n_all
                                else (16, 48, 7))
    keys = jax.random.split(jax.random.PRNGKey(21), 5)
    ex = {"w_gate": jax.random.normal(keys[0], (held, d_model, F)),
          "w_up": jax.random.normal(keys[1], (held, d_model, F)),
          "w_down": jax.random.normal(keys[2], (held, F, d_model))}
    h = jax.random.normal(keys[3], (n_tok, d_model)) / np.sqrt(d_model)
    w = jax.random.uniform(keys[4], (n_tok, k), minval=0.5, maxval=2.0)
    ids, live = _walk_case(case, n_tok, k, n_all)

    out, sizes = jax.jit(lambda *a: ds.routed_experts(ex, *a, cfg))(
        h, jnp.asarray(ids), w, jnp.asarray(live))

    want, on = _experts_by_hand(ex, h, w, ids, live, held)
    np.testing.assert_array_equal(np.asarray(sizes), on)
    assert np.abs(want).max() > 1
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4, atol=1e-4)
    assert np.abs(np.asarray(out)[~live]).max(initial=0) == 0
    counted = dict(zip(ds.COUNTERS, ds.count_routed(
        [jnp.int32(0)] * len(ds.COUNTERS), jnp.asarray(live), sizes, True,
        cfg)))
    trips = 1 if most == 1 else -(-int(on.sum()) // slab)
    assert int(counted["pairs_worked"]) == trips * slab
    assert int(counted["experts_touched"]) == (on > 0).sum()
    reads = _reads_by_hand(on, tm, slab, tiles_k)
    assert int(counted["expert_reads"]) == reads
    if tiles_k == 1 and most == 1:
        assert reads == (on > 0).sum()            # each once a call
    if case == "a group across three row tiles" and tiles_k > 1:
        assert reads > (on > 0).sum() + 1


def test_the_shares_add_up_to_the_uncut_layer(model, reference):
    """Four chips hold experts 0-3, 4-7, 8-11, 12-15 of one layer.  The
    routed parts the four shares compute, plus the shared experts
    counted once, are the uncut layer of the reference."""
    cfg, _ = model
    key = jax.random.PRNGKey(5)
    whole = ds.init_params(
        ds.DeepseekV2Config(**{**cfg.__dict__, "experts_held": 16,
                               "expert_offset": 0}), key, jnp.float32
    )["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(6), (23, 32))
    ids, w = ds.route(whole["router"], h, cfg)
    live = jnp.ones((23,), bool)
    total = decode._swiglu(whole["shared"], h, jnp.float32)
    touched = 0
    for share in range(4):
        cfg_s = ds.DeepseekV2Config(**{**cfg.__dict__,
                                       "expert_offset": 4 * share})
        mine = jax.tree_util.tree_map(lambda a: a[4 * share:4 * share + 4],
                                      whole["experts"])
        part, sizes = ds.routed_experts(mine, h, ids, w, live, cfg_s)
        touched += int(np.asarray(sizes).sum())
        total = total + part
    assert touched == 23 * K                       # every pair, once
    uncut = dict(C, n_routed_experts=16, expert_offset=0)
    with jax.default_matmul_precision("highest"):
        want = reference.moe(h, whole, uncut)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5)


CONTROLS = {"top-k less one": {"_top_k": K - 1},
            "no group limit": {"_no_group_limit": True},
            "routed_scaling_factor 1": {"_routed_scale": 1.0},
            "no shared experts": {"_no_shared": True},
            "no YaRN factor in the softmax scale": {"_no_yarn_scale": True}}


@pytest.mark.parametrize("control", list(CONTROLS) + ["float8 matmuls"])
def test_each_control_is_another_model(model, reference, control):
    """What tools/dsv2_limits.py sets the cell's limits from: the
    reference computed wrong in one way is not what the program
    computes, by far more than the program differs from the reference."""
    cfg, params = model
    got, toks, _ = _through_the_programs("partial-last-chunk", cfg, params)
    kw = {"round_to": "float8_e4m3fn"} if control == "float8 matmuls" else {}
    wrong = np.asarray(reference.forward(
        params, jnp.asarray(toks), dict(C, **CONTROLS.get(control, {})),
        query_block=16, **kw))
    assert np.abs(got - wrong).max() > 1e-3, control


# ---------------------------------------------- the benchmark's files

def test_the_benchmarks_init_is_the_programs(arch, model):
    cfg, _ = model
    key = jax.random.PRNGKey(11)
    gain = np.float32(arch.SEEDED_ATTN_LOGIT_STD
                      / arch.seeded_attn_logit_std(cfg))
    assert gain > 1
    for dtype in (jnp.float32, jnp.bfloat16):
        ours = jax.jit(lambda k: arch.init(cfg, k, dtype))(key)
        theirs = jax.jit(lambda k: ds.init_params(cfg, k, dtype))(key)
        assert jax.tree_util.tree_structure(ours) \
            == jax.tree_util.tree_structure(theirs)
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(ours),
                                jax.tree_util.tree_leaves(theirs)):
            assert a.dtype == b.dtype and a.shape == b.shape
            peaked = path[-1].key == "q_norm"
            np.testing.assert_array_equal(
                np.asarray(a, np.float32),
                np.asarray(b, np.float32) * (gain if peaked else 1))
    # at the published sizes a seeded attention logit has a standard
    # deviation of 1.13 with every norm at one
    real = ds.DeepseekV2Config(max_seq=8)
    assert arch.seeded_attn_logit_std(real) == pytest.approx(1.128, abs=2e-3)


def test_the_reference_imports_jax_alone():
    with open(os.path.join(ARCH_DIR, "reference.py")) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add("." if node.level else node.module.split(".")[0])
    assert imported <= {"__future__", "jax", "math"}, imported


def test_the_architecture_fails_by_name_on_a_program_without_the_model(
        monkeypatch):
    real = importlib.util.find_spec
    monkeypatch.setattr(
        importlib.util, "find_spec",
        lambda name, *a: None if name == "ray_tpu.models.deepseek_v2"
        else real(name, *a))
    spec = importlib.util.spec_from_file_location(
        "dsv2_arch_probe", os.path.join(ARCH_DIR, "__init__.py"),
        submodule_search_locations=[ARCH_DIR])
    mod = importlib.util.module_from_spec(spec)
    with pytest.raises(ImportError, match="ray_tpu.models.deepseek_v2"):
        spec.loader.exec_module(mod)


def test_no_other_configuration_imports_the_model():
    """Nothing this model brings runs at import or at replica start for
    another configuration: `ray_tpu.models` does not import it, nor do
    the engine, decode or the dense architecture."""
    code = ("import sys; import ray_tpu.models, ray_tpu.serve.llm.engine; "
            "sys.path.insert(0, %r); "
            "from benchmarks.lib.registry import arch_of; arch_of({}); "
            "bad = [m for m in sys.modules if 'deepseek' in m "
            "or 'megablox' in m]; assert not bad, bad" % REPO)
    import subprocess
    subprocess.run([sys.executable, "-c", code], check=True, timeout=300,
                   env=dict(os.environ, JAX_PLATFORMS="cpu"))


def _real_config():
    with open(os.path.join(BENCH, "configs",
                           "deepseek-v2-ep4-d5.json")) as f:
        return json.load(f)


def test_costs_against_hand_counts(arch):
    c = _real_config()
    attn = 5120 * 1536 + 1536 * 24576 + 5120 * 576 + 512 * 32768 \
        + 16384 * 5120
    expert = 3 * 5120 * 1536
    assert (attn, expert) == (149_225_472, 23_592_960)
    assert arch.attention_params(c) == attn
    assert arch.expert_params(c) == expert
    assert arch.layer_matmul_params(c, "dense") == attn + 3 * 5120 * 12288
    beside = attn + 2 * expert + 5120 * 160            # 197 M a layer
    assert arch.layer_matmul_params(c, "moe") == beside
    # ISSUE 35's count: 0.676 + 4 x 2.28 + 0.52 = 10.33 GB resident
    resident = (attn + 3 * 5120 * 12288) + 4 * (beside + 40 * expert) \
        + 2 * 25600 * 5120
    assert arch.matmul_params(c) + 25600 * 5120 == resident
    assert resident <= arch.total_params(c) < resident + 200_000
    assert 10.32e9 < arch.weight_bytes(c) < 10.34e9
    # a cached token is 1,152 B a layer and occupies 1,280 on the device
    assert arch.latent_bytes_per_token(c) == 5 * 1152
    assert arch.kv_bytes_per_token(c) == 5 * 1280
    # 64 rows choose 6 of 160 each: 36.5 of the 40 held experts
    assert arch.experts_touched(c, 64) == pytest.approx(
        40 * (1 - (1 - 6 / 160) ** 64))
    assert 36.4 < arch.experts_touched(c, 64) < 36.6
    assert arch.experts_touched(c, 512) > 39.99
    # a tick of 64 rows at 3.5k each: ~10.7 GB, memory-bound
    tick = arch.decode_tick(c, 64, 64 * 3500)
    fixed = (attn + 3 * 5120 * 12288) + 4 * beside + 25600 * 5120
    want = (fixed - 4 * 5120 * 160) * 2 + 4 * 5120 * 160 * 4 \
        + 64 * (5120 * 2 + 5 * 1152) \
        + 4 * (64 * (5120 * 2 + 160 * 4)
               + arch.experts_touched(c, 64) * expert * 2
               + 64 * 6 * 0.25 * 2 * 5120 * 2) \
        + 5 * 1152 * (64 * 3500 + 64)
    assert tick["bytes"] == pytest.approx(want, rel=1e-9)
    assert 10.4e9 < tick["bytes"] < 11.0e9
    assert tick["flops"] / 197e12 < tick["bytes"] / 819e9
    # the absorbed form: 2 x (576 + 512) a query, head and key; on the
    # v5e's ridge (240 FLOP / B)
    a = arch.mla_absorb_attend(c, 64, 64 * 3500)
    assert a["flops"] == 5 * 2 * 128 * 1088 * (64 * 3500 + 64)
    assert a["flops"] / a["bytes"] == pytest.approx(241.8, abs=0.1)
    # a 512-token chunk is bound by its weights, all 40 experts read
    chunk = arch.prefill_chunk(c, 512, 1536, with_head=False)
    assert chunk["bytes"] > (fixed - 25600 * 5120 + 4 * 39.9 * expert) * 2
    assert chunk["flops"] / 197e12 < chunk["bytes"] / 819e9
    for kernel, args in (("moe_route", (64,)), ("moe_experts", (64,)),
                         ("mla_expand_attend", (512, 1536))):
        cost = getattr(arch, kernel)(c, *args)
        assert cost["flops"] > 0 and cost["bytes"] > 0, kernel
    with pytest.raises(NotImplementedError, match="serves only"):
        arch.train_flops_per_token(c, 4096)


def test_the_configuration_file_holds_the_catalogs_numbers(arch):
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        rows = [json.loads(ln) for ln in f if ln.strip()]
    row = next(r for r in rows if r["name"] == "DeepSeek-V2")
    c = _real_config()
    assert c["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if c.get(k) != v)
    assert differs == sorted(c["reduced"]) \
        == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert c["published"] == {k: row["config"][k] for k in c["reduced"]}
    # the floors: 4 expert layers, 8 experts, an eighth of the vocabulary
    assert c["num_hidden_layers"] - c["first_k_dense_replace"] >= 4
    assert c["n_routed_experts"] == 40 and c["vocab_size"] == 25600
    cfg = arch.build(c, c["serving"]["engine"]["max_seq"], remat=False)
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.expert_offset) \
        == (160, 40, 0)
    assert cfg == ds.DeepseekV2Config(
        max_seq=7680, n_layers=5, vocab_size=25600, experts_held=40)


# ------------------------------------------------------------- guards

@pytest.fixture(scope="module")
def served(model):
    cfg, params = model
    eng = GenerationEngine(params, cfg, num_slots=ROWS, page_size=PSZ,
                           prefill_chunk=CHUNK, kv_pages=64)
    assert eng._prefix is not None and not eng._tiering
    yield eng
    eng.stop()


@pytest.mark.parametrize("what", [
    "kv_tiering", "kv_export", "kv_import", "session", "session_resurrect",
    "migrate_local", "speculation", "prefill_chunk"])
def test_what_frames_a_page_refuses_a_latent_page_by_name(model, served,
                                                          what):
    cfg, params = model
    kw = dict(num_slots=ROWS, page_size=PSZ, prefill_chunk=CHUNK)
    missing = "DeepseekV2Config: a latent page.*opaque bytes"
    if what == "kv_tiering":
        with pytest.raises(NotImplementedError, match=missing):
            GenerationEngine(params, cfg, kv_tiering=True, **kw)
    elif what == "kv_export":
        with pytest.raises(NotImplementedError, match=missing):
            served.kv_export([1, 2, 3])
    elif what == "kv_import":
        with pytest.raises(NotImplementedError, match=missing):
            served.kv_import([1, 2, 3], np.zeros(1), np.zeros(1))
    elif what == "session":
        with pytest.raises(NotImplementedError, match=missing):
            served.submit([1, 2, 3], max_new_tokens=2, session_id="s")
    elif what == "session_resurrect":
        with pytest.raises(NotImplementedError, match=missing):
            served.session_resurrect("s")
    elif what == "migrate_local":
        with pytest.raises(NotImplementedError, match=missing):
            kv_transfer.migrate_local(served, served, [1, 2, 3])
    elif what == "speculation":
        with pytest.raises(NotImplementedError, match="absorbed latent"):
            GenerationEngine(params, cfg, speculate_k=2, **kw)
    else:
        with pytest.raises(ValueError, match="whole latent pages"):
            GenerationEngine(params, cfg, **dict(kw, prefill_chunk=12))


def test_the_dense_body_still_refuses_experts():
    cfg = gpt.GPTConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=1,
                        d_ff=64, max_seq=32, n_experts=2)
    with pytest.raises(NotImplementedError, match="dense"):
        GenerationEngine({}, cfg)


def test_the_engine_serves_it_and_counts(model, served, reference):
    """Five requests on three rows: greedy tokens equal the reference's
    argmax chain, and the routing counters are the reference's own
    routing of the tokens the engine ran."""
    cfg, params = model
    before = served.stats().to_dict()
    prompts = [_tokens(n, seed=n).tolist() for n in (77, 20, 45, 60, 33)]
    outs = [s.result(timeout=300) for s in
            [served.submit(p, max_new_tokens=6) for p in prompts]]
    local = 0
    for p, out in zip(prompts, outs):
        # the engine ran the prompt and five of the six new tokens: one
        # causal forward over those gives every token it chose
        seq = jnp.asarray(list(p) + out[:5], jnp.int32)
        logits, routes = reference.forward(params, seq, C, query_block=16,
                                           with_routes=True)
        assert out == np.asarray(logits)[len(p) - 1:].argmax(-1).tolist()
        routes = np.asarray(routes)
        local += int(((routes >= 4) & (routes < 8)).sum())
    gain = {k: v - before[k] for k, v in served.stats().to_dict().items()
            if isinstance(v, (int, float))}
    ran = sum(len(p) + 5 for p in prompts)
    assert gain["moe_pairs_routed"] == ran * K * N_MOE
    assert gain["moe_pairs_local"] == local
    assert gain["moe_load_mean"] == pytest.approx(local / 4)
    assert gain["moe_load_max"] >= gain["moe_load_mean"]
    assert 0 < gain["moe_experts_touched"] <= gain["moe_experts_held"]
    assert gain["moe_experts_held"] % (4 * N_MOE) == 0
    assert gain["prefill_tokens"] == sum(map(len, prompts))
    # it attends to all it holds, and gathers whole spans for every row
    assert gain["attn_keys_attended"] == gain["attn_keys_resident"] > 0
    assert gain["attn_keys_gathered"] > gain["attn_keys_resident"]
    assert gain["prefill_tokens_sparse"] == gain["state_resets"] == 0


def test_the_prefix_cache_shares_latent_pages(model, served):
    """The radix cache hands out page ids: a second prompt that begins
    as the first skips those pages' prefill and decodes the same."""
    head = _tokens(40, seed=77).tolist()
    a = served.submit(head + [5, 6, 7], max_new_tokens=4).result(timeout=300)
    before = served.stats().prefix_hit_tokens
    b = served.submit(head + [5, 6, 7], max_new_tokens=4).result(timeout=300)
    assert served.stats().prefix_hit_tokens - before == 40
    assert a == b
    assert served._lander is None       # no tiers: no lander thread


# ------------------------------------- the toy configuration as a cell

def test_the_toy_configuration_is_served_to_correct(tmp_path):
    """A temporary benchmark root gets a configuration that names
    `deepseek_v2`, a decode-heavy mix at toy size and a cell; the
    benchmark's own run serves it, its check (48 + 4 positions: three
    expanded chunks, four absorbed ticks) comes out correct, and the
    three expert metrics read the program's counters."""
    sys.path.insert(0, os.path.join(BENCH, "tests"))
    try:
        import toy
    finally:
        sys.path.pop(0)
    from benchmarks import run as bench_run
    from benchmarks.lib.registry import Registry

    root = toy.build(str(tmp_path))
    b = os.path.join(root, "bm")
    with open(os.path.join(b, "configs", "toy-dsv2.json"), "w") as f:
        json.dump(C, f)
    with open(os.path.join(b, "traffic", "moe-toy.json"), "w") as f:
        json.dump({"kind": "serve", "loop": "closed", "clients": 6,
                   "block": 3, "blocks": 64, "warmup_first_tokens": 3,
                   "prompt_len": {"dist": "lognormal", "median": 40,
                                  "sigma": 0.3, "min": 20, "max": 80},
                   "output_len": {"dist": "fixed", "value": 12},
                   "trace_seconds": 1}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "toy-dsv2", "source": "none",
                            "file": "bm/configs/toy-dsv2.json",
                            "reduced": [], "why": "toy"})
    spec["workloads"].append({"name": "dsv2-toy", "config": "toy-dsv2",
                              "traffic": "moe-toy", "chips": 1,
                              "why": "toy"})
    for m in spec["end_to_end"]:
        if m["name"] == "out_tok_per_s":
            m["workloads"].append("dsv2-toy")
    for m in spec["per_layer"]:
        if m["name"].endswith(".moe"):
            m["workloads"] = ["dsv2-toy"]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    reg = Registry(root)
    lines = []
    out = bench_run.run_cell(reg, "dsv2-toy", seed=2**31 + 35,
                             seconds=4.0, trace=False, platform="cpu",
                             init_kwargs={"num_cpus": 6}, emit=lines.append)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"out_tok_per_s", "setup_s"}
    check = json.loads(lines[0])["check"]
    assert check["finite"] and check["positions"] == 52
    assert check["max_abs_diff"] <= 1e-3 and check["argmax_equal"] == 52
    # the data-only metric files, on counters as a run's stats() holds
    obs = {"stats0": {k: 0 for k in (
        "moe_pairs_routed", "moe_pairs_local", "moe_experts_touched",
        "moe_experts_held", "moe_load_max", "moe_load_mean")},
        "stats1": {"moe_pairs_routed": 1200, "moe_pairs_local": 300,
                   "moe_experts_touched": 90, "moe_experts_held": 100,
                   "moe_load_max": 30, "moe_load_mean": 7.5}}
    read = {m: reg.reader(reg.metric(m)["reader"])(
        obs, **reg.metric(m)["args"]) for m in (
        "expert_local_share.moe", "experts_touched_share.moe",
        "expert_load_peak.moe")}
    assert read == {"expert_local_share.moe": 25.0,
                    "experts_touched_share.moe": 90.0,
                    "expert_load_peak.moe": 4.0}
    # ...and a parent without the counters reads nothing, quietly
    assert reg.reader("stats_delta")({"stats0": {}, "stats1": {}},
                                     ["moe_pairs_local"],
                                     ["moe_pairs_routed"], 100) is None
