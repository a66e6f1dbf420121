"""K-EXAONE (`exaone_moe`) at toy widths on the CPU, seeded weights: the
engine's own two programs (chunks, then ticks that wrap the window
layers' rings) against one forward of the plain reference, two rows in
one tick and a row admitted into a slot another sequence left, the
sigmoid router against a transcription of its equations, the expert
layer's eight shares against the uncut layer, what a window layer holds
and reads, the refusals by name, the controls a comparison must catch,
the benchmark's architecture files against the program, and the toy
configuration served to `correct` from a temporary benchmark root."""

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
from ray_tpu.models import exaone_moe as em
from ray_tpu.serve.llm import engine as engine_mod
from ray_tpu.serve.llm import kv_transfer
from ray_tpu.serve.llm.engine import GenerationEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
ARCH_DIR = os.path.join(BENCH, "archs", "exaone_moe")
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# The toy configuration, as a benchmark file would state it: a window of
# 8 in the pattern LLLG (layers 0-2 and 4 are window layers, 3 is
# global), layer 0 dense, 16 routed experts top-4 of which experts 2..3
# are held here (the second of eight shares).
W, L = 8, 5
C = {
    "name": "toy-kexaone", "arch": "exaone_moe",
    "first_k_dense_replace": 1, "head_dim": 8, "hidden_size": 32,
    "intermediate_size": 64,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 2,
    "mlp_layer_types": ["dense"] + ["sparse"] * 7,
    "moe_intermediate_size": 16, "n_group": 1, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts": 2, "expert_offset": 2,
    "num_experts_per_tok": 4, "num_hidden_layers": L,
    "num_key_value_heads": 2, "num_shared_experts": 1,
    "rms_norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 10000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "sliding_window": W, "sliding_windows": [W, W, W, 0] * 2,
    "topk_group": 1, "vocab_size": 128, "torch_dtype": "float32",
    "published": {"num_experts": 16, "num_hidden_layers": 8,
                  "vocab_size": 512},
    "serving": {"engine": {"num_slots": 3, "max_seq": 128, "page_size": 4,
                           "kv_pages": 96, "prefill_chunk": 12,
                           "enable_prefix_cache": False},
                "check": {"prompt_len": 36, "decode_tokens": 10,
                          "tolerance": {"max_abs_diff": 1e-3,
                                        "mean_abs_diff": 1e-4}}}}
ROWS = 3
K, N_MOE = C["num_experts_per_tok"], L - 1


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "kexaone_" + name, os.path.join(ARCH_DIR, name + ".py"))
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


def _bumped(params, seed=8):
    """Norms that are not all ones and a selection bias that is not
    zero, so a missing one shows."""
    bump = iter(jax.random.split(jax.random.PRNGKey(seed), 128))
    return jax.tree_util.tree_map(
        lambda w: w if w.ndim != 1 else
        w + 0.1 * jax.random.normal(next(bump), w.shape), params)


@pytest.fixture(scope="module")
def model(arch):
    cfg = arch.build(C, C["serving"]["engine"]["max_seq"], remat=False)
    return cfg, _bumped(arch.init(cfg, jax.random.PRNGKey(7), jnp.float32))


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        1, C["vocab_size"], size=n).astype(np.int32)


class Driver:
    """The engine's two jitted programs over one cache, driven by hand
    as the engine's admission and tick do."""

    def __init__(self, cfg, params, psz, chunk, pages=96, nblk=32):
        self.cfg, self.params, self.psz, self.chunk = cfg, params, psz, chunk
        self.cache = decode.init_paged_cache(cfg, pages + 1, psz, ROWS)
        self.bt = np.zeros((ROWS, nblk), np.int32)
        self.pos = np.zeros((ROWS,), np.int32)
        self.tok = np.zeros((ROWS,), np.int32)
        self.next_page = 1

    def admit(self, slot, toks, total):
        """Prefill `toks` into fresh pages for `total` tokens, chunk by
        chunk, then activate row `slot`."""
        n = -(-total // self.psz)
        row = np.zeros((self.bt.shape[1],), np.int32)
        row[:n] = np.arange(self.next_page, self.next_page + n)
        self.next_page += n
        rows = []
        for s in range(0, len(toks), self.chunk):
            real = toks[s:s + self.chunk]
            chunk = np.zeros((1, self.chunk), np.int32)
            chunk[0, :len(real)] = real
            logits, self.cache = engine_mod._prefill_chunk(
                self.params, jnp.asarray(chunk), jnp.int32(s), self.cache,
                jnp.asarray(row[None]), self.cfg, slot=jnp.int32(slot),
                valid=jnp.int32(len(real)))
            rows.append(np.asarray(logits[0, :len(real)]))
        self.bt[slot], self.pos[slot] = row, len(toks)
        return np.concatenate(rows)

    def leave(self, slot):
        self.bt[slot], self.pos[slot], self.tok[slot] = 0, 0, 0

    def tick(self, feed):
        """One tick; `feed` {slot: token}.  Returns the logits [B, V]."""
        for slot, t in feed.items():
            self.tok[slot] = t
        _, logits, self.cache = engine_mod._paged_tick(
            self.params, jnp.asarray(self.tok), jnp.asarray(self.pos),
            self.cache, jnp.asarray(self.bt), self.cfg, with_logits=True)
        logits = np.asarray(logits)
        assert np.isfinite(logits).all()       # idle rows too
        for slot in feed:
            self.pos[slot] += 1
        return logits


def _one_sequence(drv, slot, toks, n_prompt):
    """Logits of every position of `toks`: the prompt through chunks,
    the rest tick by tick."""
    rows = [drv.admit(slot, toks[:n_prompt], len(toks))]
    for t in toks[n_prompt:]:
        rows.append(drv.tick({slot: t})[slot][None])
    return np.concatenate(rows)


# ------------------------------------ the engine's programs = one forward

CASES = {
    # page, chunk, prompt, ticks: chunk boundaries at 12 (inside a
    # window, on a page edge), 24, 36; a padded last chunk; 20 ticks
    # wrap the ring of 8 twice
    "chunk-12-page-4": (4, 12, 41, 20),
    # a chunk of two windows runs block by block (2 x 8 queries)
    "chunk-16-page-8": (8, 16, 53, 12),
    "whole-chunks": (4, 12, 36, 9),
    # a prompt shorter than the window: the ring is partly empty
    "short-prompt": (4, 12, 5, 14),
}


@pytest.mark.parametrize("case", list(CASES))
def test_prefill_chunks_then_ticks_are_one_reference_forward(
        model, reference, case, tick_attention):
    """A prompt longer than two windows and two pages in several chunks,
    then ticks that wrap the rings: every position's logits against the
    reference's full-mask forward, so a chunk reads what earlier chunks
    left in the ring and in the pages, a padded last chunk leaves no pad
    in the ring and routes none, and a ring that wrapped holds exactly
    the window."""
    cfg, params = model
    psz, chunk, n_prompt, n_decode = CASES[case]
    drv = Driver(cfg, params, psz, chunk)
    toks = _tokens(n_prompt + n_decode, seed=len(case))
    got = _one_sequence(drv, 1, toks, n_prompt)
    want, routes = reference.forward(params, jnp.asarray(toks), C,
                                     query_block=16, width_blocks=2,
                                     with_routes=True)
    assert np.asarray(want).std() > 0.05
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-5)
    # the program's own counters are the reference's routing
    routes = np.asarray(routes)
    counts = em.read_counters(drv.cache, cfg)
    assert counts["pairs_routed"] == routes.size == len(toks) * K * N_MOE
    assert counts["pairs_local"] == int(((routes >= 2) & (routes < 4)).sum())
    assert counts["experts_held"] == n_decode * N_MOE * 2
    assert 0 < counts["experts_touched"] <= counts["experts_held"]


# sha256 over the float32 and bfloat16 logits, rings and pages of the
# four CASES as the program stood at PR 49 (commit ff20a51), taken on
# that commit's own code and again on PR 51's, on this container's CPU.
PR49_DIGEST = "c012394a01a5b4f70dcf6d0ff6614fe9425986d64c670fc6540989638ee4496a"
# ...and two of those logits, for a machine whose CPU rounds otherwise
PR49_SAMPLE = (520.5582275390625, 216.810302734375)


def test_the_generalised_functions_leave_kexaone_bit_equal(arch):
    """PR 51 taught this module's attention a second width, a head count
    a kind, partial RoPE, a value scale and a sink, for
    models/mimo_v2_flash.py.  K-EXAONE runs the defaults, and its
    numbers are what they were: every logit, ring entry and page of the
    four cases, in float32 and in bfloat16, to the bit."""
    import hashlib
    cfg = arch.build(C, 128, remat=False)
    digest, sums = hashlib.sha256(), []
    for dtype in (jnp.float32, jnp.bfloat16):
        params = _bumped(arch.init(cfg, jax.random.PRNGKey(7), dtype))
        typed = em.ExaoneMoeConfig(**{**cfg.__dict__, "dtype": dtype})
        for case, (psz, chunk, n_prompt, n_decode) in CASES.items():
            toks = _tokens(n_prompt + n_decode, seed=len(case))
            drv = Driver(typed, params, psz, chunk)
            got = _one_sequence(drv, 1, toks, n_prompt)
            for a in (got, drv.cache["wk"], drv.cache["k"]):
                digest.update(np.asarray(a, np.float32).tobytes())
            sums.append(float(np.abs(got).sum()))
    if digest.hexdigest() != PR49_DIGEST:
        np.testing.assert_allclose(sums[-2:], PR49_SAMPLE, rtol=1e-3)
        pytest.skip("this CPU rounds otherwise than the one the digest "
                    "was taken on; the numbers agree")


def test_a_model_of_window_layers_alone_holds_no_page(arch, reference):
    """Every layer a window layer: the pool has no layer at all, and
    logits 60 tokens in are still the reference's, so a window layer
    keeps nothing a token."""
    c = dict(C, sliding_windows=[W] * 8, layer_types=["sliding_attention"] * 8)
    cfg = arch.build(c, 128, remat=False)
    params = _bumped(arch.init(cfg, jax.random.PRNGKey(3), jnp.float32))
    drv = Driver(cfg, params, 4, 12)
    assert drv.cache["k"].shape[0] == 0 and drv.cache["wk"].shape[:3] == (
        L, ROWS, W)
    toks = _tokens(60, seed=4)
    got = _one_sequence(drv, 0, toks, 40)
    want = reference.forward(params, jnp.asarray(toks), c, query_block=16)
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-5)


def test_two_rows_in_one_tick_and_a_slot_that_changes_hands(model,
                                                            reference):
    """Rows 0 and 2 decode at different depths in the same ticks; row 2's
    sequence ends and a SHORTER one (5 tokens: less than a window) is
    admitted into its slot while row 0 goes on: what the earlier
    sequence left in the slot's rings is never read, and the ticks of
    row 0 during the prefill leave the new row's rings alone."""
    cfg, params = model
    drv = Driver(cfg, params, 4, 12)
    a, b, c2 = _tokens(70, seed=1), _tokens(33, seed=2), _tokens(21, seed=3)
    got_a = [drv.admit(0, a[:30], len(a))]
    got_b = [drv.admit(2, b[:20], len(b))]
    for i in range(13):                            # both rows tick
        out = drv.tick({0: a[30 + i], 2: b[20 + i]})
        got_a.append(out[0][None])
        got_b.append(out[2][None])
    drv.leave(2)
    # row 0 ticks on between the new row's admission and its first tick
    got_c = [drv.admit(2, c2[:5], len(c2))]
    row, drv.bt[2], drv.pos[2] = drv.bt[2].copy(), 0, 0   # not yet active
    out = drv.tick({0: a[43]})
    got_a.append(out[0][None])
    drv.bt[2], drv.pos[2] = row, 5
    for i in range(16):
        out = drv.tick({0: a[44 + i], 2: c2[5 + i]})
        got_a.append(out[0][None])
        got_c.append(out[2][None])
    for got, toks in ((got_a, a[:60]), (got_b, b), (got_c, c2)):
        want = reference.forward(params, jnp.asarray(toks), C,
                                 query_block=16)
        np.testing.assert_allclose(np.concatenate(got), np.asarray(want),
                                   atol=2e-5)


# ------------------------------------------------------------- the routing

def test_routing_against_the_equations():
    """sigmoid over all, the top-4 of score + bias chosen, weights the
    scores renormalised over the four chosen x 2.5 — the bias chooses
    and does not weigh."""
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(6, 16)) * 2
    bias = rng.normal(size=16) * 0.3
    cfg = em.ExaoneMoeConfig(max_seq=8, n_layers=1, d_model=6,
                             n_routed_experts=16, top_k=4,
                             sliding_windows=(0,))
    ids, w = em.route(jnp.asarray(logits, jnp.float32),
                      jnp.asarray(bias, jnp.float32), jnp.eye(6), cfg)
    ids, w = np.asarray(ids), np.asarray(w)
    s = 1 / (1 + np.exp(-logits))
    for n in range(6):
        want = np.argsort(-(s[n] + bias))[:4]
        assert sorted(ids[n]) == sorted(want)
        np.testing.assert_allclose(
            w[n], 2.5 * s[n, ids[n]] / (s[n, ids[n]].sum() + 1e-20),
            rtol=1e-5)
        assert w[n].sum() == pytest.approx(2.5, rel=1e-5)
    assert any(sorted(ids[n]) != sorted(np.argsort(-s[n])[:4])
               for n in range(6))                  # the bias moved a choice


def test_a_token_none_of_whose_experts_is_held_gets_the_shared_expert(
        model, reference):
    """The router is made to send every token to experts 8-11; this
    share holds 2-3: the routed part is zero, nothing stands in for the
    absent experts, and the weights still sum to 2.5 over the four
    chosen elsewhere."""
    cfg, params = model
    lp = dict(params["layers"][1])
    lp["router_bias"] = jnp.zeros((16,)).at[8:12].set(100.0)
    h = jax.random.normal(jax.random.PRNGKey(3), (9, 32))
    ids, w = em.route(lp["router"], lp["router_bias"], h, cfg)
    assert set(np.asarray(ids).ravel()) == {8, 9, 10, 11}
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-5)
    out, sizes = em._ds.routed_experts(lp["experts"], h, ids, w,
                                       jnp.ones((9,), bool), cfg)
    assert int(np.asarray(sizes).sum()) == 0
    assert np.abs(np.asarray(out)).max() == 0
    with jax.default_matmul_precision("highest"):
        whole = reference.moe(h, lp, C)
        shared = decode._swiglu(lp["shared"], h, jnp.float32)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(shared),
                               atol=1e-5)


def test_the_eight_shares_add_up_to_the_uncut_layer(model, reference):
    """Eight chips hold experts 0-1, 2-3, ... 14-15 of one layer.  The
    routed parts the eight shares compute, plus the shared expert
    counted once, are the uncut layer of the reference."""
    cfg, _ = model
    uncut_cfg = em.ExaoneMoeConfig(**{**cfg.__dict__, "experts_held": 16,
                                      "expert_offset": 0})
    whole = _bumped(em.init_params(uncut_cfg, jax.random.PRNGKey(5),
                                   jnp.float32))["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(6), (23, 32))
    ids, w = em.route(whole["router"], whole["router_bias"], h, cfg)
    live = jnp.ones((23,), bool)
    total = decode._swiglu(whole["shared"], h, jnp.float32)
    touched = 0
    for share in range(8):
        cfg_s = em.ExaoneMoeConfig(**{**cfg.__dict__,
                                      "expert_offset": 2 * share})
        mine = jax.tree_util.tree_map(lambda a: a[2 * share:2 * share + 2],
                                      whole["experts"])
        part, sizes = em._ds.routed_experts(mine, h, ids, w, live, cfg_s)
        touched += int(np.asarray(sizes).sum())
        total = total + part
    assert touched == 23 * K                       # every pair, once
    uncut = dict(C, num_experts=16, expert_offset=0)
    with jax.default_matmul_precision("highest"):
        want = reference.moe(h, whole, uncut)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5)


# ------------------------------- what a window layer holds and reads

def test_a_window_layer_holds_and_reads_at_most_its_window():
    """At the published sizes, cut to the configuration's five layers:
    positions 0, 127, 128 and 10,000."""
    cfg = em.ExaoneMoeConfig(max_seq=14336, n_layers=5)
    assert (cfg.window, cfg.n_window, cfg.n_global) == (128, 4, 1)
    for pos, want in ((0, 1 + 4 * 1), (127, 128 + 4 * 128),
                      (128, 129 + 4 * 128), (10000, 10001 + 4 * 128)):
        assert em.attn_keys(cfg, np.array([pos])) == (want, want)
    read, held = em.attn_keys(cfg, np.array([0, 127, 128, 10000]))
    assert read == held == 5 + 640 + 641 + 10513
    # a tick pulls whole spans of the global layer for every row, and
    # 128 ring entries a row and window layer whatever the depth
    cols = em._TICK_SPAN_KEYS
    spans = -(-10001 // cols)
    assert em.attn_keys_gathered(cfg, np.array([0, 127, 128, 10000]), 64,
                                 224) == 4 * (spans * cols + 4 * 128)
    shapes = jax.eval_shape(lambda: em.init_paged_cache(cfg, 9101, 64, 128))
    assert shapes["k"].shape == shapes["v"].shape == (1, 9101, 64, 8, 128)
    assert shapes["wk"].shape == shapes["wv"].shape == (4, 128, 128, 8, 128)
    # 128 rows' rings are 268 MB whatever the context
    ring = 2 * np.prod(shapes["wk"].shape) * 2
    assert ring == 128 * 4 * 128 * 4096 == 268_435_456


def test_the_pool_and_the_reservation_count_global_layers_only(model):
    cfg, params = model
    eng = GenerationEngine(params, cfg, num_slots=ROWS, page_size=4,
                           prefill_chunk=12, kv_pages=32,
                           enable_prefix_cache=False)
    try:
        assert eng._blocks_for(30, 10) == 10       # tokens / page, once
        assert eng._cache["k"].shape == (cfg.n_global, 33, 4, 2, 8)
        assert eng._cache["wk"].shape == (cfg.n_window, ROWS, W, 2, 8)
        # 32 pages of ONE layer hold a 120-token sequence: five layers
        # paged alike would need 150 pages' worth of bytes
        out = eng.submit(_tokens(70, seed=9).tolist(),
                         max_new_tokens=50).result(timeout=300)
        assert len(out) == 50
    finally:
        eng.stop()


def test_a_tick_leaves_an_idle_rows_ring_alone(model):
    cfg, params = model
    drv = Driver(cfg, params, 4, 12)
    drv.admit(1, _tokens(20, seed=1), 40)
    before = np.asarray(drv.cache["wk"])
    drv.bt[1], drv.pos[1] = 0, 0                   # filled, not yet active
    drv.admit(0, _tokens(9, seed=2), 20)
    drv.tick({0: 5})
    after = np.asarray(drv.cache["wk"])
    np.testing.assert_array_equal(after[:, 1:], before[:, 1:])
    assert np.abs(after[:, 0] - before[:, 0]).max() > 0


# ------------------------------------------------------------ the controls

CONTROLS = {"window ignored": {"_window_ignored": True},
            "window of half": {"_window": W // 2},
            "RoPE in the global layer": {"_rope_global": True},
            "no QK-norm": {"_no_qk_norm": True},
            "top-k less one": {"_top_k": K - 1},
            "no renormalisation": {"_no_renorm": True},
            "routed_scaling_factor 1": {"_routed_scale": 1.0},
            "no shared expert": {"_no_shared": True}}


@pytest.mark.parametrize("control", list(CONTROLS) + ["float8 matmuls"])
def test_each_control_is_another_model(model, reference, control):
    """What tools/kexaone_limits.py sets the cell's limits from: the
    reference computed wrong in one way is not what the program
    computes, by far more than the program differs from the reference."""
    cfg, params = model
    psz, chunk, n_prompt, n_decode = CASES["chunk-12-page-4"]
    toks = _tokens(n_prompt + n_decode, seed=1)
    got = _one_sequence(Driver(cfg, params, psz, chunk), 0, toks, n_prompt)
    kw = {"round_to": "float8_e4m3fn"} if control == "float8 matmuls" else {}
    wrong = np.asarray(reference.forward(
        params, jnp.asarray(toks), dict(C, **CONTROLS.get(control, {})),
        query_block=16, **kw))
    assert np.abs(got - wrong).max() > 1e-3, control


# ---------------------------------------------- the benchmark's files

def test_the_benchmarks_init_is_the_programs(arch, model):
    cfg, _ = model
    key = jax.random.PRNGKey(11)
    gain = np.float32(arch.SEEDED_ATTN_LOGIT_STD ** 0.5)
    for dtype in (jnp.float32, jnp.bfloat16):
        ours = jax.jit(lambda k: arch.init(cfg, k, dtype))(key)
        theirs = jax.jit(lambda k: em.init_params(cfg, k, dtype))(key)
        assert jax.tree_util.tree_structure(ours) \
            == jax.tree_util.tree_structure(theirs)
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(ours),
                                jax.tree_util.tree_leaves(theirs)):
            assert a.dtype == b.dtype and a.shape == b.shape
            peaked = path[-1].key in ("qn", "kn")
            np.testing.assert_array_equal(
                np.asarray(a, np.float32),
                np.asarray(b, np.float32) * (gain if peaked else 1))
    # layers are a tuple: each layer's experts are an array of their own
    assert isinstance(ours["layers"], tuple)
    assert ours["layers"][1]["experts"]["w_gate"].shape == (2, 32, 16)
    assert float(jnp.abs(ours["layers"][1]["router_bias"]).max()) == 0


def test_the_benchmarks_seeded_attention_shows_the_window(arch, reference):
    """With the norms' weights at one seeded attention is near uniform
    and ignoring the window moves little; peaked (the benchmark's init)
    it moves the logits by a large share of their spread."""
    cfg = arch.build(C, 128, remat=False)
    toks = jnp.asarray(_tokens(64, seed=5))
    moved = {}
    for name, params in (
            ("flat", em.init_params(cfg, jax.random.PRNGKey(2), jnp.float32)),
            ("peaked", arch.init(cfg, jax.random.PRNGKey(2), jnp.float32))):
        a = np.asarray(reference.forward(params, toks, C, query_block=16))
        b = np.asarray(reference.forward(
            params, toks, dict(C, _window_ignored=True), query_block=16))
        moved[name] = np.abs(a - b)[W:].mean() / a.std()
    assert moved["peaked"] > 1.5 * moved["flat"] > 0


def test_the_reference_imports_jax_alone():
    with open(os.path.join(ARCH_DIR, "reference.py")) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add("." if node.level else node.module.split(".")[0])
    assert imported <= {"__future__", "jax"}, imported


def test_the_architecture_fails_by_name_on_a_program_without_the_model(
        monkeypatch):
    real = importlib.util.find_spec
    monkeypatch.setattr(
        importlib.util, "find_spec",
        lambda name, *a: None if name == "ray_tpu.models.exaone_moe"
        else real(name, *a))
    spec = importlib.util.spec_from_file_location(
        "kexaone_arch_probe", os.path.join(ARCH_DIR, "__init__.py"),
        submodule_search_locations=[ARCH_DIR])
    mod = importlib.util.module_from_spec(spec)
    with pytest.raises(ImportError, match="ray_tpu.models.exaone_moe"):
        spec.loader.exec_module(mod)


def test_no_other_configuration_imports_the_model():
    """Nothing this model brings runs at import or at replica start for
    another configuration: `ray_tpu.models` does not import it, nor do
    the engine, decode, the dense architecture or DeepSeek-V2's."""
    code = ("import sys; import ray_tpu.models, ray_tpu.serve.llm.engine; "
            "sys.path.insert(0, %r); "
            "from benchmarks.lib.registry import arch_of; arch_of({}); "
            "arch_of({'arch': 'deepseek_v2'}); "
            "bad = [m for m in sys.modules if 'exaone' in m]; "
            "assert not bad, bad" % REPO)
    import subprocess
    subprocess.run([sys.executable, "-c", code], check=True, timeout=300,
                   env=dict(os.environ, JAX_PLATFORMS="cpu"))


def _real_config():
    with open(os.path.join(BENCH, "configs", "k-exaone-ep8-d5.json")) as f:
        return json.load(f)


def test_costs_against_hand_counts(arch):
    c = _real_config()
    attn = 6144 * 8192 + 2 * 6144 * 1024 + 8192 * 6144
    expert = 3 * 6144 * 2048
    assert (attn, expert) == (113_246_208, 37_748_736)
    assert arch.attention_params(c) == attn
    assert arch.expert_params(c) == expert
    assert arch.layer_matmul_params(c, "dense") == attn + 3 * 6144 * 18432
    beside = attn + expert + 6144 * 128
    assert arch.layer_matmul_params(c, "moe") == beside
    # ISSUE 38's table: 0.906 + 4 x 1.512 + 0.472 = 7.42 GB resident
    resident = (attn + 3 * 6144 * 18432) + 4 * (beside + 16 * expert) \
        + 2 * 19200 * 6144
    assert arch.matmul_params(c) + 19200 * 6144 == resident
    assert resident <= arch.total_params(c) < resident + 100_000
    assert arch.weight_bytes(c) == pytest.approx(7.42e9, rel=0.005)
    # a token occupies 4,096 B in pages (one global layer of five); a
    # row's rings are 2.1 MB whatever its context
    assert arch.token_layer_bytes(c) == 4096
    assert arch.kv_bytes_per_token(c) == 4096
    assert arch.ring_bytes_per_row(c) == 4 * 128 * 4096
    # 128 rows choose 8 of 128 each: every held expert gets tokens
    assert arch.experts_touched(c, 128) == pytest.approx(
        16 * (1 - (1 - 8 / 128) ** 128))
    assert arch.experts_touched(c, 128) > 15.99
    # a tick of 128 rows at 3.4k each: weights once, the global layer
    # over the context, the window layers over 128 a row
    rows, ctx = 128, 128 * 3400
    tick = arch.decode_tick(c, rows, ctx)
    fixed = (attn + 3 * 6144 * 18432) + 4 * beside + 19200 * 6144
    want = (fixed - 4 * 6144 * 128) * 2 + 4 * 6144 * 128 * 4 \
        + rows * (6144 * 2 + 5 * 4096) \
        + 4 * (rows * (6144 * 2 + 128 * 4)
               + arch.experts_touched(c, rows) * expert * 2
               + rows * 8 * 0.125 * 2 * 6144 * 2) \
        + 4096 * (ctx + rows) + 4 * 4096 * rows * 128
    assert tick["bytes"] == pytest.approx(want, rel=1e-9)
    assert tick["flops"] / 197e12 < tick["bytes"] / 819e9
    g, w = arch.attn_global(c, rows, ctx), arch.attn_window(c, rows, ctx)
    assert g["bytes"] == 4096 * (ctx + rows)
    assert w["bytes"] == 4 * 4096 * rows * 128      # not the context
    # ...which a masked full-length cache would read 4 x over
    assert 4 * g["bytes"] > 20 * w["bytes"]
    # rows that are still inside the window hold what they have
    assert arch.attn_window(c, 2, 2 * 9)["bytes"] == 4 * 4096 * 2 * 10
    # a 512-token chunk after 2,048 tokens: a window layer scores 128
    # keys a query, the global layer the context
    wc = arch.attn_window_chunk(c, 512, 2048)
    assert wc["flops"] == 4 * 2 * 64 * 2 * 128 * 512 * 128
    assert wc["bytes"] == 4 * 4096 * (127 + 512)
    first = arch.attn_window_chunk(c, 512, 0)
    assert first["flops"] == 4 * 2 * 64 * 2 * 128 * (
        128 * 129 // 2 + (512 - 128) * 128)
    gc = arch.attn_global_chunk(c, 512, 2048)
    assert gc["flops"] == 2 * 64 * 2 * 128 * 512 * (2048 + 513 / 2)
    chunk = arch.prefill_chunk(c, 512, 2048, with_head=False)
    assert chunk["bytes"] > (fixed - 19200 * 6144 + 4 * 15.9 * expert) * 2
    for kernel, args in (("moe_route", (128,)), ("moe_experts", (128,))):
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
    row = next(r for r in rows if r["name"] == "K-EXAONE-236B-A23B")
    c = _real_config()
    assert c["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if c.get(k) != v)
    assert differs == sorted(c["reduced"]) \
        == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert c["published"] == {k: row["config"][k] for k in c["reduced"]}
    # the floors: 4 expert layers with one whole LLLG period, 8 experts,
    # an eighth of the vocabulary
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) \
        == (5, 16, 19200)
    assert c["sliding_windows"][:5] == [128, 128, 128, 0, 128]
    assert "8 v5e chips" in c["stands_for"]
    for key in ("assumed", "departures", "resident_bytes", "reduced_why"):
        assert c[key], key
    assert any("multi-token" in d for d in c["departures"])
    cfg = arch.build(c, c["serving"]["engine"]["max_seq"], remat=False)
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.expert_offset) \
        == (128, 16, 0)
    assert cfg == em.ExaoneMoeConfig(
        max_seq=c["serving"]["engine"]["max_seq"], n_layers=5,
        vocab_size=19200, experts_held=16)


def test_the_new_cells_files_load_through_the_registry():
    from benchmarks.lib.registry import Registry
    reg = Registry(REPO)
    cell = reg.cell("kexaone-reason")
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("k-exaone-ep8-d5", "moe_reason", 1)
    c, mix = reg.config(cell["config"]), reg.traffic(cell["traffic"])
    assert c["arch"] == "exaone_moe"
    assert (mix["loop"], mix["clients"], mix["block"],
            mix["warmup_first_tokens"], mix["trace_seconds"]) \
        == ("closed", 256, 128, 128, 6)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 2048,
                                 "sigma": 0.5, "min": 512, "max": 8192}
    assert mix["output_len"] == {"dist": "lognormal", "median": 2048,
                                 "sigma": 0.4, "min": 768, "max": 6144}
    e = c["serving"]["engine"]
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] \
        <= e["max_seq"]
    assert mix["block"] == e["num_slots"] and not e["enable_prefix_cache"]
    names = {m["name"] for m in reg.metrics_for("kexaone-reason",
                                                "per_layer")}
    assert "kv_held_share.kx" in names and "replica_start_s" in names
    assert {m["name"] for m in reg.metrics_for(
        "kexaone-reason", "end_to_end")} == {"out_tok_per_s", "setup_s"}
    for name in names:
        spec = reg.metric(name)
        reg.reader(spec["reader"])
    held = reg.metric("kv_held_share.kx")
    obs = {"stats0": {"attn_keys_resident": 0, "attn_keys_context": 0},
           "stats1": {"attn_keys_resident": 230, "attn_keys_context": 1000}}
    assert reg.reader(held["reader"])(obs, **held["args"]) == 23.0
    # a parent without the counter reads nothing, quietly
    assert reg.reader(held["reader"])(
        {"stats0": {"attn_keys_resident": 0},
         "stats1": {"attn_keys_resident": 5}}, **held["args"]) is None


# ------------------------------------------------------------- guards

@pytest.fixture(scope="module")
def served(model):
    cfg, params = model
    eng = GenerationEngine(params, cfg, num_slots=ROWS, page_size=4,
                           prefill_chunk=12, kv_pages=96,
                           enable_prefix_cache=False)
    yield eng
    eng.stop()


@pytest.mark.parametrize("what", [
    "prefix_cache", "kv_tiering", "kv_export", "kv_import", "session",
    "session_resurrect", "migrate_local", "speculation", "prefill_chunk"])
def test_what_cannot_carry_a_ring_refuses_by_name(model, served, what):
    cfg, params = model
    kw = dict(num_slots=ROWS, page_size=4, prefill_chunk=12,
              enable_prefix_cache=False)
    missing = "per-row recurrent state .ExaoneMoeConfig."
    if what == "prefix_cache":
        with pytest.raises(NotImplementedError, match=missing):
            GenerationEngine(params, cfg, **dict(kw, enable_prefix_cache=True))
    elif what == "kv_tiering":
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
        with pytest.raises(NotImplementedError, match="rolled back"):
            GenerationEngine(params, cfg, speculate_k=2, **kw)
    else:
        with pytest.raises(ValueError, match="whole pages"):
            GenerationEngine(params, cfg, **dict(kw, prefill_chunk=10))


def test_the_engine_serves_it_and_counts(model, served, reference):
    """Five requests on three rows (slots change hands): greedy tokens
    equal the reference's argmax chain, the routing counters are the
    reference's own routing, and what the rows hold is less than their
    context once they pass the window."""
    cfg, params = model
    before = served.stats().to_dict()
    prompts = [_tokens(n, seed=n).tolist() for n in (41, 5, 30, 17, 22)]
    outs = [s.result(timeout=300) for s in
            [served.submit(p, max_new_tokens=14) for p in prompts]]
    local = context = held = 0
    for p, out in zip(prompts, outs):
        seq = jnp.asarray(list(p) + out[:13], jnp.int32)
        logits, routes = reference.forward(params, seq, C, query_block=16,
                                           with_routes=True)
        assert out == np.asarray(logits)[len(p) - 1:].argmax(-1).tolist()
        routes = np.asarray(routes)
        local += int(((routes >= 2) & (routes < 4)).sum())
        for pos in range(len(p), len(p) + 13):     # the ticks' positions
            context += (pos + 1) * L
            held += (pos + 1) + 4 * min(pos + 1, W)
    gain = {k: v - before[k] for k, v in served.stats().to_dict().items()
            if isinstance(v, (int, float))}
    ran = sum(len(p) + 13 for p in prompts)
    assert gain["moe_pairs_routed"] == ran * K * N_MOE
    assert gain["moe_pairs_local"] == local
    assert gain["prefill_tokens"] == sum(map(len, prompts))
    assert gain["attn_keys_context"] == context
    assert gain["attn_keys_attended"] == gain["attn_keys_resident"] == held
    assert held < 0.6 * context
    assert gain["attn_keys_gathered"] >= gain["attn_keys_resident"]
    assert gain["state_resets"] == 5 and gain["prefill_tokens_sparse"] == 0


def test_every_other_model_holds_all_its_context():
    """`attn_keys_context` for the dense body: resident / context is 1."""
    from ray_tpu.models import llama
    cfg = llama.LlamaConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_kv_heads=2, n_layers=2, d_ff=64, max_seq=64,
                            dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    eng = GenerationEngine(params, cfg, num_slots=2, page_size=4,
                           prefill_chunk=8, kv_pages=32)
    try:
        eng.submit([1, 2, 3, 4, 5], max_new_tokens=6).result(timeout=300)
        st = eng.stats()
        assert st.attn_keys_context == st.attn_keys_resident > 0
    finally:
        eng.stop()


# ------------------------------------- the toy configuration as a cell

def test_the_toy_configuration_is_served_to_correct(tmp_path):
    """A temporary benchmark root gets a configuration that names
    `exaone_moe`, a reasoning mix at toy size and a cell; the
    benchmark's own run serves it, its check (36 + 10 positions: three
    chunks, ten ticks that wrap the rings) comes out correct, and
    `kv_held_share` reads the program's counters."""
    sys.path.insert(0, os.path.join(BENCH, "tests"))
    try:
        import toy
    finally:
        sys.path.pop(0)
    from benchmarks import run as bench_run
    from benchmarks.lib.registry import Registry

    root = toy.build(str(tmp_path))
    b = os.path.join(root, "bm")
    with open(os.path.join(b, "configs", "toy-kexaone.json"), "w") as f:
        json.dump(C, f)
    with open(os.path.join(b, "traffic", "reason-toy.json"), "w") as f:
        json.dump({"kind": "serve", "loop": "closed", "clients": 6,
                   "block": 3, "blocks": 64, "warmup_first_tokens": 3,
                   "prompt_len": {"dist": "lognormal", "median": 24,
                                  "sigma": 0.3, "min": 12, "max": 48},
                   "output_len": {"dist": "fixed", "value": 20},
                   "trace_seconds": 1}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "toy-kexaone", "source": "none",
                            "file": "bm/configs/toy-kexaone.json",
                            "reduced": [], "why": "toy"})
    spec["workloads"].append({"name": "kx-toy", "config": "toy-kexaone",
                              "traffic": "reason-toy", "chips": 1,
                              "why": "toy"})
    for m in spec["end_to_end"]:
        if m["name"] == "out_tok_per_s":
            m["workloads"].append("kx-toy")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    reg = Registry(root)
    lines = []
    out = bench_run.run_cell(reg, "kx-toy", seed=2**31 + 38,
                             seconds=4.0, trace=False, platform="cpu",
                             init_kwargs={"num_cpus": 6}, emit=lines.append)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"out_tok_per_s", "setup_s"}
    check = json.loads(lines[0])["check"]
    assert check["finite"] and check["positions"] == 46
    assert check["max_abs_diff"] <= 1e-3 and check["argmax_equal"] == 46
