"""MiMo-V2-Flash (`mimo_v2_flash`) at toy widths on the CPU, seeded
weights with peaked attention and sinks that are not zero: the engine's
own two programs (chunks, then ticks that wrap the window layers' rings)
against one forward of the plain reference at sizes that keep every
ratio (keys 12 wide and values 8, 2 key-value heads in full layers and 4
in window layers, RoPE on 4 of 12, 7 layers in the published pattern),
two rows in one tick and a row that changes hands, the 16 shares of the
expert layer against the uncut layer, the controls a comparison must
catch, what the engine reports of a pool whose values are narrower than
its keys, the refusals by name, the benchmark's architecture files
against the program, and the toy configuration served to `correct`."""

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
from ray_tpu.models import mimo_v2_flash as mm
from ray_tpu.serve.llm import engine as engine_mod
from ray_tpu.serve.llm import kv_transfer
from ray_tpu.serve.llm.engine import GenerationEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
ARCH_DIR = os.path.join(BENCH, "archs", "mimo_v2_flash")
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# The toy configuration, as a benchmark file would state it: the first 7
# layers of the published pattern (full, window x 4, full, window), layer
# 0 dense, 32 routed experts top-4 of which experts 2..3 are held here
# (the second of 16 shares).
W, L = 8, 7
PATTERN = [0, 1, 1, 1, 1, 0] + [1, 1, 1, 1, 1, 0]
C = {
    "name": "toy-mimo", "arch": "mimo_v2_flash",
    "attention_value_scale": 0.707, "hidden_size": 32,
    "intermediate_size": 64, "num_attention_heads": 8, "head_dim": 12,
    "num_hidden_layers": L, "num_key_value_heads": 2,
    "layernorm_epsilon": 1e-5, "rope_theta": 5000000, "vocab_size": 128,
    "partial_rotary_factor": 0.334, "sliding_window": W,
    "swa_rope_theta": 10000, "attention_bias": False, "v_head_dim": 8,
    "hybrid_layer_pattern": PATTERN,
    "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False, "sliding_window_size": W,
    "attention_chunk_size": W, "moe_layer_freq": [0] + [1] * 11,
    "moe_intermediate_size": 16, "n_routed_experts": 2, "expert_offset": 2,
    "n_shared_experts": None, "num_experts_per_tok": 4,
    "norm_topk_prob": True, "scoring_func": "sigmoid", "n_group": 1,
    "topk_group": 1, "topk_method": "noaux_tc",
    "routed_scaling_factor": None, "swa_num_attention_heads": 8,
    "swa_num_key_value_heads": 4, "swa_head_dim": 12, "swa_v_head_dim": 8,
    "torch_dtype": "float32",
    "published": {"n_routed_experts": 32, "num_hidden_layers": 12,
                  "vocab_size": 512},
    "serving": {"engine": {"num_slots": 3, "max_seq": 128, "page_size": 4,
                           "kv_pages": 96, "prefill_chunk": 12,
                           "enable_prefix_cache": False},
                "check": {"prompt_len": 36, "decode_tokens": 10,
                          "tolerance": {"max_abs_diff": 1e-4,
                                        "mean_abs_diff": 1e-5}}}}
ROWS = 3
K, N_MOE, N_WINDOW, N_FULL, HEADS = 4, L - 1, 5, 2, 8


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "mimo_" + name, os.path.join(ARCH_DIR, name + ".py"))
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
    zero, so a missing one shows (the sinks are drawn already)."""
    bump = iter(jax.random.split(jax.random.PRNGKey(seed), 128))
    return jax.tree_util.tree_map(
        lambda w: w if w.ndim != 1 else
        w + 0.1 * jax.random.normal(next(bump), w.shape), params)


@pytest.fixture(scope="module")
def model(arch):
    cfg = arch.build(C, C["serving"]["engine"]["max_seq"], remat=False)
    return cfg, _bumped(arch.init(cfg, jax.random.PRNGKey(7), jnp.float32))


def dataclass_with(cfg, **changes):
    return mm.MimoV2FlashConfig(**{**cfg.__dict__, **changes})


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
        chunk (the last one padded), then activate row `slot`."""
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
    # window, on a page edge), 24, 36; a padded last chunk; the prompt
    # wraps the ring of 8 five times and 20 ticks wrap it twice more
    "chunk-12-page-4": (4, 12, 41, 20),
    # a chunk of two windows runs block by block (2 x 8 queries)
    "chunk-16-page-8": (8, 16, 53, 12),
    "whole-chunks": (4, 12, 36, 9),
    # a prompt shorter than the window: the ring is partly empty and the
    # sink competes with few keys
    "short-prompt": (4, 12, 5, 14),
}


@pytest.mark.parametrize("case", list(CASES))
def test_prefill_chunks_then_ticks_are_one_reference_forward(
        model, reference, case, tick_attention):
    """Every position's logits against the reference's full-mask
    forward: a chunk reads what earlier chunks left in the rings (8
    heads) and in the pages (2 heads, values narrower than keys), a
    padded last chunk leaves no pad in the ring and routes none, and a
    ring that wrapped holds exactly the window."""
    cfg, params = model
    psz, chunk, n_prompt, n_decode = CASES[case]
    drv = Driver(cfg, params, psz, chunk)
    toks = _tokens(n_prompt + n_decode, seed=len(case))
    got = _one_sequence(drv, 1, toks, n_prompt)
    want, routes = reference.forward(params, jnp.asarray(toks), C,
                                     query_block=16, width_blocks=2,
                                     with_routes=True)
    assert np.asarray(want).std() > 0.05
    np.testing.assert_allclose(got, np.asarray(want), atol=3e-5)
    # the program's own counters are the reference's routing, and every
    # window softmax of a real token counted its sink
    routes = np.asarray(routes)
    counts = mm._em.read_counters(drv.cache, cfg)
    assert counts["pairs_routed"] == routes.size == len(toks) * K * N_MOE
    assert counts["pairs_local"] == int(((routes >= 2) & (routes < 4)).sum())
    assert counts["experts_held"] == n_decode * N_MOE * 2
    assert counts["attn_sink_softmaxes"] == len(toks) * HEADS * N_WINDOW
    share = counts["attn_sink_mass"] / counts["attn_sink_softmaxes"]
    assert 0.05 < share < 0.95


def test_two_rows_in_one_tick_and_a_slot_that_changes_hands(model,
                                                            reference):
    """Rows 0 and 2 decode at different depths in the same ticks; row 2's
    sequence ends and a SHORTER one (5 tokens: less than a window, one
    padded chunk) is admitted into its slot while row 0 goes on: what
    the earlier sequence left in the slot's rings is never read (nothing
    zeroes them), so the new sequence's logits are a fresh engine's."""
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
                                   atol=3e-5)
    fresh = _one_sequence(Driver(cfg, params, 4, 12), 2, c2, 5)
    np.testing.assert_allclose(np.concatenate(got_c), fresh, atol=1e-6)


# ------------------------------------------------------ the expert layer

def test_the_sixteen_shares_add_up_to_the_uncut_layer(model, reference):
    """Sixteen chips hold experts 0-1, 2-3, ... 30-31 of one layer.  The
    routed parts the sixteen shares compute (there is no shared expert
    to count once) are the uncut layer of the reference."""
    cfg, _ = model
    uncut_cfg = dataclass_with(cfg, experts_held=32, expert_offset=0)
    whole = _bumped(mm.init_params(uncut_cfg, jax.random.PRNGKey(5),
                                   jnp.float32))["layers"][1]
    assert "shared" not in whole
    h = jax.random.normal(jax.random.PRNGKey(6), (23, 32))
    ids, w = mm._em.route(whole["router"], whole["router_bias"], h, cfg)
    live = jnp.ones((23,), bool)
    total, touched = 0, 0
    for share in range(16):
        cfg_s = dataclass_with(cfg, expert_offset=2 * share)
        mine = jax.tree_util.tree_map(lambda a: a[2 * share:2 * share + 2],
                                      whole["experts"])
        part, sizes = mm._em._ds.routed_experts(mine, h, ids, w, live, cfg_s)
        touched += int(np.asarray(sizes).sum())
        total = total + part
    assert touched == 23 * K                       # every pair, once
    uncut = dict(C, n_routed_experts=32, expert_offset=0)
    with jax.default_matmul_precision("highest"):
        want = reference.moe(h, whole, uncut)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5)
    # the weights are renormalised over the four chosen and scaled by 1
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-6)


# ------------------------------------------------------------ the controls

CONTROLS = {"sink left out": {"_no_sink": True},
            "value scale left out": {"_no_v_scale": True},
            "the two thetas swapped": {"_thetas_swapped": True},
            "rotary over the whole head": {"_rotary_dim": 12},
            "window less one": {"_window": W - 1},
            "top-k less one": {"_top_k": K - 1}}


@pytest.fixture(scope="module")
def served_logits(model):
    """(tokens, the program's logits at every position) of one case."""
    cfg, params = model
    psz, chunk, n_prompt, n_decode = CASES["chunk-12-page-4"]
    toks = _tokens(n_prompt + n_decode, seed=1)
    return toks, _one_sequence(Driver(cfg, params, psz, chunk), 0, toks,
                               n_prompt)


@pytest.mark.parametrize("control", list(CONTROLS) + ["float8 matmuls"])
def test_each_control_is_another_model(model, reference, served_logits,
                                       control):
    """What tools/mimo_limits.py sets the cell's limits from: the
    reference computed wrong in one way is not what the program
    computes: each fails the toy cell's tolerance (1e-4 and 1e-5, which
    the program itself meets by a factor of 500), and all but the
    mildest (one expert fewer of four, where 2 of 32 are held) fail both
    limits tenfold."""
    params = model[1]
    toks, got = served_logits
    kw = {"round_to": "float8_e4m3fn"} if control == "float8 matmuls" else {}
    wrong = np.asarray(reference.forward(
        params, jnp.asarray(toks), dict(C, **CONTROLS.get(control, {})),
        query_block=16, **kw))
    limit = C["serving"]["check"]["tolerance"]
    diff = np.abs(got - wrong)
    over = min(diff.max() / limit["max_abs_diff"],
               diff.mean() / limit["mean_abs_diff"])
    assert over > (1.2 if control == "top-k less one" else 10), control


# ------------------------------------------ what the engine reports

def _real_config():
    with open(os.path.join(BENCH, "configs",
                           "mimo-v2-flash-ep16-d7.json")) as f:
        return json.load(f)


def test_a_page_is_both_arrays_and_a_ring_is_a_rows(arch, model):
    """The engine's pool bytes are pages x page_size x what a token
    occupies in the k AND the v pages (a value is two thirds of a key:
    twice the k pages would be 20 % too many), and its row state is the
    two ring arrays; at the published widths a token is 5,120 B and a
    row's five rings 3.28 MB."""
    cfg, params = model
    cfg16 = dataclass_with(cfg, dtype=jnp.bfloat16)
    eng = GenerationEngine(
        jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params),
        cfg16, num_slots=ROWS, page_size=4, prefill_chunk=12, kv_pages=32,
        enable_prefix_cache=False)
    try:
        st = eng.stats()
        assert arch.kv_bytes_per_token(C) == N_FULL * 2 * (12 + 8) * 2
        assert st.kv_pool_bytes == 32 * 4 * arch.kv_bytes_per_token(C)
        assert st.kv_pool_bytes != 32 * 2 * int(
            eng._cache["k"].nbytes) // 33
        assert st.row_state_bytes == ROWS * arch.ring_bytes_per_row(C) \
            == eng._cache["wk"].nbytes + eng._cache["wv"].nbytes
        assert eng._page_kshape is None and eng._page_k_nbytes is None
        assert eng._blocks_for(30, 10) == 10       # tokens / page, once
    finally:
        eng.stop()
    c = _real_config()
    e = c["serving"]["engine"]
    real = arch.build(c, e["max_seq"], remat=False)
    shapes = jax.eval_shape(lambda: decode.init_paged_cache(
        real, e["kv_pages"] + 1, e["page_size"], e["num_slots"]))
    P, psz, B = e["kv_pages"] + 1, e["page_size"], e["num_slots"]
    # four arrays of four shapes; a token's heads lie side by side
    assert shapes["k"].shape == (2, P, psz, 4 * 192)
    assert shapes["v"].shape == (2, P, psz, 4 * 128)
    assert shapes["wk"].shape == (5, B, 128, 8 * 192)
    assert shapes["wv"].shape == (5, B, 128, 8 * 128)
    nbytes = lambda s: int(np.prod(s.shape)) * s.dtype.itemsize  # noqa: E731
    assert arch.kv_bytes_per_token(c) == 5120
    assert nbytes(shapes["k"]) + nbytes(shapes["v"]) == P * psz * 5120
    assert arch.ring_bytes_per_row(c) == 5 * 128 * 8 * 320 * 2 == 3_276_800
    assert nbytes(shapes["wk"]) + nbytes(shapes["wv"]) == B * 3_276_800


def test_the_dense_pools_frame_is_what_it_was():
    """The dense body's page frame (what tiers and migration ship) is
    derived as before: K then V of [L, psz, Hkv, Dh]."""
    from ray_tpu.models import llama
    cfg = llama.LlamaConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_kv_heads=2, n_layers=2, d_ff=64, max_seq=64,
                            dtype=jnp.float32)
    eng = GenerationEngine(llama.init_params(cfg, jax.random.PRNGKey(0)),
                           cfg, num_slots=2, page_size=4, prefill_chunk=8,
                           kv_pages=32)
    try:
        assert eng._page_kshape == (2, 4, 2, 8)
        assert eng._page_k_nbytes == 2 * 4 * 2 * 8 * 4
        assert eng._page_nbytes == 2 * eng._page_k_nbytes
        assert eng.stats().kv_pool_bytes == 32 * eng._page_nbytes
        assert decode.paged_read_batch(eng._cache) == 64
    finally:
        eng.stop()


def test_costs_against_hand_counts(arch):
    c = _real_config()
    full = 4096 * 12288 + 4096 * 768 + 4096 * 512 + 8192 * 4096
    window = 4096 * 12288 + 4096 * 1536 + 4096 * 1024 + 8192 * 4096
    expert = 3 * 4096 * 2048
    assert (full, window, expert) == (89_128_960, 94_371_840, 25_165_824)
    assert arch.attention_params(c, False) == full
    assert arch.attention_params(c, True) == window
    assert arch.expert_params(c) == expert
    # ISSUE 51's table: layer 0 290.46 M, layer 5 492.83 M, layers 1-4
    # and 6 498.07 M each, embedding + head 156.24 M: 3.43 B = 6.86 GB
    router = 4096 * 256
    resident = (full + 3 * 4096 * 16384) + (full + router + 16 * expert) \
        + 5 * (window + router + 16 * expert) + 2 * 19072 * 4096
    assert arch.matmul_params(c) + 19072 * 4096 == resident
    assert resident <= arch.total_params(c) < resident + 100_000
    assert arch.weight_bytes(c) == pytest.approx(6.86e9, rel=0.005)
    # ...and what the arrays hold
    cfg = arch.build(c, c["serving"]["engine"]["max_seq"], remat=False)
    shapes = jax.eval_shape(
        lambda k: arch.init(cfg, k, cfg.dtype), jax.random.PRNGKey(0))
    held = sum(int(np.prod(s.shape)) * s.dtype.itemsize
               for s in jax.tree_util.tree_leaves(shapes))
    assert arch.weight_bytes(c) == pytest.approx(held, rel=0.005)
    assert arch.token_layer_bytes(c, False) == 2560
    assert arch.token_layer_bytes(c, True) == 5120
    # 64 rows choose 8 of 256 each: 13.9 of the 16 held get a token; a
    # 512-token chunk reads them all
    assert arch.experts_touched(c, 64) == pytest.approx(
        16 * (1 - (1 - 8 / 256) ** 64))
    assert 13.8 < arch.experts_touched(c, 64) < 14.0
    assert arch.experts_touched(c, 512) > 15.99
    rows, ctx = 64, 64 * 5700
    tick = arch.decode_tick(c, rows, ctx)
    fixed = 2 * full + 5 * window + 3 * 4096 * 16384 + 6 * router \
        + 19072 * 4096
    want = (fixed - 6 * router) * 2 + 6 * router * 4 \
        + rows * (4096 * 2 + 2 * 2560 + 5 * 5120) \
        + 6 * (rows * (4096 * 2 + 256 * 4)
               + arch.experts_touched(c, rows) * expert * 2
               + rows * 8 / 16 * 2 * 4096 * 2) \
        + 2 * 2560 * (ctx + rows) + 5 * 5120 * rows * 128
    assert tick["bytes"] == pytest.approx(want, rel=1e-9)
    assert tick["flops"] / 197e12 < tick["bytes"] / 819e9
    g, w = arch.attn_global(c, rows, ctx), arch.attn_window(c, rows, ctx)
    assert g["bytes"] == 5120 * (ctx + rows)
    assert g["flops"] == 2 * 2 * 64 * (192 + 128) * (ctx + rows)
    assert w["bytes"] == 5 * 5120 * rows * 128      # not the context
    # a 512-token chunk after 2,048 tokens: a window layer scores 128
    # keys a query, the full layers the context
    wc = arch.attn_window_chunk(c, 512, 2048)
    assert wc["flops"] == 5 * 2 * 64 * 320 * 512 * 128
    assert wc["bytes"] == 5 * 5120 * (127 + 512)
    gc = arch.attn_global_chunk(c, 512, 2048)
    assert gc["flops"] == 2 * 2 * 64 * 320 * 512 * (2048 + 513 / 2)
    chunk = arch.prefill_chunk(c, 512, 2048, with_head=False)
    assert chunk["bytes"] > (fixed - 19072 * 4096 + 6 * 15.9 * expert) * 2
    for kernel, args in (("moe_route", (64,)), ("moe_experts", (64,))):
        cost = getattr(arch, kernel)(c, *args)
        assert cost["flops"] > 0 and cost["bytes"] > 0, kernel
    with pytest.raises(NotImplementedError, match="serves only"):
        arch.train_flops_per_token(c, 4096)


# ---------------------------------------------- the benchmark's files

def test_the_benchmarks_init_is_the_programs(arch, model):
    cfg, _ = model
    key = jax.random.PRNGKey(11)
    gain = (arch.SEEDED_ATTN_LOGIT_STD / (0.02 ** 2 * 32)) ** 0.5
    for dtype in (jnp.float32, jnp.bfloat16):
        ours = jax.jit(lambda k: arch.init(cfg, k, dtype))(key)
        theirs = jax.jit(lambda k: mm.init_params(cfg, k, dtype))(key)
        assert jax.tree_util.tree_structure(ours) \
            == jax.tree_util.tree_structure(theirs)
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(ours),
                                jax.tree_util.tree_leaves(theirs)):
            assert a.dtype == b.dtype and a.shape == b.shape
            name = path[-1].key
            if name == "sink":
                assert float(jnp.abs(b).max()) == 0
                assert 1.0 < float(a.std()) < 3.5      # 8 draws of std 2
                continue
            # (the compiler may fold the two scalings of a draw into
            # one, and the rounding between them away: equal to one
            # rounding of the type, not to the bit)
            scaled = name in ("wq", "wk")
            np.testing.assert_allclose(
                np.asarray(a, np.float32),
                np.asarray(b, np.float32) * (gain if scaled else 1),
                rtol=float(jnp.finfo(dtype).eps) if scaled else 0)
    # layers are a tuple: each layer's experts are an array of their own;
    # a full layer has no sink and no layer a shared expert or a q / k norm
    assert isinstance(ours["layers"], tuple)
    assert ours["layers"][1]["experts"]["w_gate"].shape == (2, 32, 16)
    assert ours["layers"][0]["wk"].shape == (32, 2, 12)
    assert ours["layers"][0]["wv"].shape == (32, 2, 8)
    assert ours["layers"][1]["wk"].shape == (32, 4, 12)
    assert ours["layers"][1]["wo"].shape == (8, 8, 32)
    assert [("sink" in lp) for lp in ours["layers"]] == [
        bool(p) for p in PATTERN[:L]]
    assert not {"shared", "qn", "kn", "wkv"} & set(ours["layers"][1])
    assert float(jnp.abs(ours["layers"][1]["router_bias"]).max()) == 0


def test_the_benchmarks_seeded_weights_show_each_mechanism(arch, reference):
    """With W_q and W_k at 0.02 and the sinks at zero, seeded attention
    is near uniform and the sink takes next to nothing: leaving the sink
    out or swapping the thetas moves little.  Under the benchmark's init
    both move the logits by a visible share of their spread."""
    cfg = arch.build(C, 128, remat=False)
    toks = jnp.asarray(_tokens(64, seed=5))
    moved = {}
    for name, params in (
            ("flat", mm.init_params(cfg, jax.random.PRNGKey(2), jnp.float32)),
            ("peaked", arch.init(cfg, jax.random.PRNGKey(2), jnp.float32))):
        a = np.asarray(reference.forward(params, toks, C, query_block=16))
        for control in ("_no_sink", "_thetas_swapped"):
            b = np.asarray(reference.forward(
                params, toks, dict(C, **{control: True}), query_block=16))
            moved[name, control] = np.abs(a - b)[W:].mean() / a.std()
    for control in ("_no_sink", "_thetas_swapped"):
        assert moved["peaked", control] > 1.5 * moved["flat", control] > 0
    assert moved["peaked", "_no_sink"] > 0.05


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
        lambda name, *a: None if name == "ray_tpu.models.mimo_v2_flash"
        else real(name, *a))
    spec = importlib.util.spec_from_file_location(
        "mimo_arch_probe", os.path.join(ARCH_DIR, "__init__.py"),
        submodule_search_locations=[ARCH_DIR])
    mod = importlib.util.module_from_spec(spec)
    with pytest.raises(ImportError, match="ray_tpu.models.mimo_v2_flash"):
        spec.loader.exec_module(mod)


def test_no_other_configuration_imports_the_model():
    """Nothing this model brings runs at import or at replica start for
    another configuration: `ray_tpu.models` does not import it, nor do
    the engine, decode, or K-EXAONE's module, whose functions it runs."""
    code = ("import sys; import ray_tpu.models, ray_tpu.serve.llm.engine; "
            "sys.path.insert(0, %r); "
            "from benchmarks.lib.registry import arch_of; arch_of({}); "
            "arch_of({'arch': 'exaone_moe'}); "
            "import ray_tpu.models.exaone_moe; "
            "bad = [m for m in sys.modules if 'mimo' in m]; "
            "assert not bad, bad" % REPO)
    import subprocess
    subprocess.run([sys.executable, "-c", code], check=True, timeout=300,
                   env=dict(os.environ, JAX_PLATFORMS="cpu"))


def test_the_configuration_file_holds_the_catalogs_numbers(arch):
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        rows = [json.loads(ln) for ln in f if ln.strip()]
    row = next(r for r in rows if r["name"] == "MiMo-V2-Flash")
    c = _real_config()
    assert c["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if c.get(k) != v)
    assert differs == sorted(c["reduced"]) \
        == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert c["published"] == {k: row["config"][k] for k in c["reduced"]}
    # the floors: layer 0 + six expert layers with one whole period of
    # the pattern at the published 5 : 1, 16 experts, an eighth of the
    # vocabulary
    assert (c["num_hidden_layers"], c["n_routed_experts"],
            c["vocab_size"]) == (7, 16, 152576 // 8)
    assert c["hybrid_layer_pattern"][:7] == [0, 1, 1, 1, 1, 0, 1]
    assert "16 v5e chips" in c["stands_for"]
    for key in ("assumed", "departures", "resident_bytes", "reduced_why"):
        assert c[key], key
    assert any("multi-token" in d for d in c["departures"])
    cfg = arch.build(c, c["serving"]["engine"]["max_seq"], remat=False)
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.expert_offset) \
        == (256, 16, 0)
    assert cfg == mm.MimoV2FlashConfig(
        max_seq=c["serving"]["engine"]["max_seq"], n_layers=7,
        vocab_size=19072, experts_held=16)
    assert (cfg.n_global, cfg.n_window, cfg.rotary_dim) == (2, 5, 64)


def test_the_new_cells_files_load_through_the_registry():
    from benchmarks.lib.registry import Registry
    reg = Registry(REPO)
    cell = reg.cell("mimo-agent")
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("mimo-v2-flash-ep16-d7", "agent", 1)
    c, mix = reg.config(cell["config"]), reg.traffic(cell["traffic"])
    assert c["arch"] == "mimo_v2_flash"
    assert (mix["loop"], mix["clients"], mix["block"],
            mix["warmup_first_tokens"], mix["trace_seconds"]) \
        == ("closed", 128, 64, 64, 6)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 4096,
                                 "sigma": 0.6, "min": 1024, "max": 24576}
    assert mix["output_len"] == {"dist": "lognormal", "median": 768,
                                 "sigma": 0.5, "min": 192, "max": 3072}
    e = c["serving"]["engine"]
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] \
        <= e["max_seq"] == 27648
    assert mix["block"] == e["num_slots"] == 64
    assert not e["enable_prefix_cache"]
    names = {m["name"] for m in reg.metrics_for("mimo-agent", "per_layer")}
    assert {"attn_gather_ratio_global.tput", "attn_sink_mass_share.tput",
            "row_state_gb.tput", "kv_held_share.kx",
            "replica_start_s"} <= names
    assert {m["name"] for m in reg.metrics_for(
        "mimo-agent", "end_to_end")} == {"out_tok_per_s", "setup_s"}
    for name in names:
        spec = reg.metric(name)
        reg.reader(spec["reader"])
    sink = reg.metric("attn_sink_mass_share.tput")
    obs = {"stats0": {"attn_sink_mass": 10.0, "attn_sink_softmaxes": 100},
           "stats1": {"attn_sink_mass": 50.0, "attn_sink_softmaxes": 200}}
    assert reg.reader(sink["reader"])(obs, **sink["args"]) == 40.0
    ratio = reg.metric("attn_gather_ratio_global.tput")
    obs = {"stats0": {"attn_keys_gathered_paged": 0,
                      "attn_keys_resident_paged": 0},
           "stats1": {"attn_keys_gathered_paged": 300,
                      "attn_keys_resident_paged": 100}}
    assert reg.reader(ratio["reader"])(obs, **ratio["args"]) == 3.0
    # a parent without the counters, or a model without a sink (its
    # counter stays 0), reads nothing, quietly
    for spec in (sink, ratio):
        assert reg.reader(spec["reader"])(
            {"stats0": {}, "stats1": {}}, **spec["args"]) is None
    still = {"attn_sink_mass": 0.0, "attn_sink_softmaxes": 0}
    assert reg.reader(sink["reader"])(
        {"stats0": still, "stats1": still}, **sink["args"]) is None


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
    missing = "per-row recurrent state .MimoV2FlashConfig."
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
    reference's own routing, the sinks' counters count every window
    softmax, and the paged layers' keys are counted apart from the
    rings'."""
    cfg, params = model
    before = served.stats().to_dict()
    prompts = [_tokens(n, seed=n).tolist() for n in (41, 5, 30, 17, 22)]
    outs = [s.result(timeout=300) for s in
            [served.submit(p, max_new_tokens=14) for p in prompts]]
    local = context = held = paged = 0
    for p, out in zip(prompts, outs):
        seq = jnp.asarray(list(p) + out[:13], jnp.int32)
        logits, routes = reference.forward(params, seq, C, query_block=16,
                                           with_routes=True)
        assert out == np.asarray(logits)[len(p) - 1:].argmax(-1).tolist()
        routes = np.asarray(routes)
        local += int(((routes >= 2) & (routes < 4)).sum())
        for pos in range(len(p), len(p) + 13):     # the ticks' positions
            context += (pos + 1) * L
            paged += (pos + 1) * N_FULL
            held += (pos + 1) * N_FULL + N_WINDOW * min(pos + 1, W)
    gain = {k: v - before[k] for k, v in served.stats().to_dict().items()
            if isinstance(v, (int, float))}
    ran = sum(len(p) + 13 for p in prompts)
    assert gain["moe_pairs_routed"] == ran * K * N_MOE
    assert gain["moe_pairs_local"] == local
    assert gain["prefill_tokens"] == sum(map(len, prompts))
    assert gain["attn_keys_context"] == context
    assert gain["attn_keys_attended"] == gain["attn_keys_resident"] == held
    assert gain["attn_keys_resident_paged"] == paged
    assert gain["attn_keys_gathered_paged"] >= paged
    # the mixed ratio is diluted by the rings; the paged one is not
    assert gain["attn_keys_gathered_paged"] / paged \
        > gain["attn_keys_gathered"] / held
    assert gain["attn_sink_softmaxes"] == ran * HEADS * N_WINDOW
    assert 0.05 < gain["attn_sink_mass"] / gain["attn_sink_softmaxes"] < 0.95
    assert gain["state_resets"] == 5 and gain["prefill_tokens_sparse"] == 0
    assert served.stats().row_state_bytes == sum(
        int(served._cache[k].nbytes)
        for k in decode.paged_body(cfg).row_state_keys)


# ------------------------------------- the toy configuration as a cell

def test_the_toy_configuration_is_served_to_correct(tmp_path):
    """A temporary benchmark root gets a configuration that names
    `mimo_v2_flash`, an agent mix at toy size and a cell; the
    benchmark's own run serves it, its check (36 + 10 positions: three
    chunks, ten ticks that wrap the rings) comes out correct, and the
    two metrics this model brings read the program's counters."""
    sys.path.insert(0, os.path.join(BENCH, "tests"))
    try:
        import toy
    finally:
        sys.path.pop(0)
    from benchmarks import run as bench_run
    from benchmarks.lib.registry import Registry

    root = toy.build(str(tmp_path))
    b = os.path.join(root, "bm")
    with open(os.path.join(b, "configs", "toy-mimo.json"), "w") as f:
        json.dump(C, f)
    with open(os.path.join(b, "traffic", "agent-toy.json"), "w") as f:
        json.dump({"kind": "serve", "loop": "closed", "clients": 6,
                   "block": 3, "blocks": 64, "warmup_first_tokens": 3,
                   "prompt_len": {"dist": "lognormal", "median": 36,
                                  "sigma": 0.4, "min": 12, "max": 72},
                   "output_len": {"dist": "fixed", "value": 12},
                   "trace_seconds": 1}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "toy-mimo", "source": "none",
                            "file": "bm/configs/toy-mimo.json",
                            "reduced": [], "why": "toy"})
    spec["workloads"].append({"name": "mimo-toy", "config": "toy-mimo",
                              "traffic": "agent-toy", "chips": 1,
                              "why": "toy"})
    for m in spec["end_to_end"]:
        if m["name"] == "out_tok_per_s":
            m["workloads"].append("mimo-toy")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    reg = Registry(root)
    lines = []
    out = bench_run.run_cell(reg, "mimo-toy", seed=2**31 + 51,
                             seconds=4.0, trace=False, platform="cpu",
                             init_kwargs={"num_cpus": 6}, emit=lines.append)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"out_tok_per_s", "setup_s"}
    check = json.loads(lines[0])["check"]
    assert check["finite"] and check["positions"] == 46
    assert check["max_abs_diff"] <= 1e-4 and check["argmax_equal"] == 46
