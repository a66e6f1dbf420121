"""Ling-3.0-flash (`bailing_hybrid`) at toy widths on the CPU, seeded
weights whose decay, write strength, router bias and gates all move the
logits: the engine's own two programs (chunks, then ticks that carry the
delta-rule state and the convolution tails) against one forward of the
plain reference, token by token, at sizes that keep every ratio (six
layers from published index 1: dense KDA, three KDA, MLA, KDA; 32
experts in 8 groups top-4 through a bias with one group held), two rows
in one tick and a slot that changes hands, the controls a comparison
must catch, the share test, the router by hand, the yardstick against
the program's shapes at the configuration's sizes, the refusals by
name, and the toy configuration served to `correct`."""

import ast
import dataclasses
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import bailing_hybrid as bh
from ray_tpu.models import decode
from ray_tpu.serve.llm import engine as engine_mod
from ray_tpu.serve.llm import kv_transfer
from ray_tpu.serve.llm.engine import GenerationEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
if REPO not in sys.path:
    sys.path.insert(0, REPO)

L, HEADS, E_ALL, HELD, D = 6, 2, 32, 4, 64
C = {
    "name": "toy-ling3", "arch": "bailing_hybrid",
    "expert_swiglu_limit_list": [0] * 12, "first_k_dense_replace": 1,
    "gated_attention_proj_granularity_type": "head_wise",
    "group_norm_size": 1, "head_dim": 16, "hidden_act": "silu",
    "hidden_size": D, "intermediate_size": 96, "kda_lower_bound": -5,
    "kda_safe_gate": True, "kv_lora_rank": 32, "layer_group_size": 6,
    "linear_silu": True, "max_position_embeddings": 4096,
    "moe_intermediate_size": 32, "moe_router_enable_expert_bias": True,
    "moe_shared_expert_intermediate_size": 32, "n_group": 8,
    "no_kda_lora": True, "norm_topk_prob": True, "num_attention_heads": HEADS,
    "num_experts": HELD, "num_experts_per_tok": 4, "num_hidden_layers": L,
    "num_key_value_heads": HEADS, "num_kv_heads_for_linear_attn": 0,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_head_dim": 24, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "rms_norm_eps": 1e-6, "rope_interleave": True,
    "rope_scaling": None, "rope_theta": 6000000, "rotary_dim": 8,
    "routed_scaling_factor": 2.5, "scale_router_input": False,
    "score_function": "sigmoid", "share_expert_swiglu_limit_list": [0] * 12,
    "short_conv_kernel_size": 4, "tie_word_embeddings": False,
    "topk_group": 4, "topk_method": "noaux_tc", "up_proj_norm": False,
    "use_bias": False, "use_kda_lora": False, "use_mla_nope": False,
    "use_nGPT": False, "use_qk_norm": True, "use_qkv_bias": False,
    "v_head_dim": 16, "value_norm": False, "vocab_size": 128,
    "model_type": "bailing_hybrid", "layer_offset": 1, "expert_offset": 0,
    "torch_dtype": "float32",
    "published": {"num_hidden_layers": 12, "first_k_dense_replace": 2,
                  "num_experts": E_ALL, "vocab_size": 1024,
                  "num_nextn_predict_layers": 1},
    "serving": {"engine": {"num_slots": 3, "max_seq": 256, "page_size": 16,
                           "kv_pages": 48, "prefill_chunk": 32,
                           "enable_prefix_cache": False},
                "check": {"prompt_len": 64, "decode_tokens": 6,
                          "tolerance": {"max_abs_diff": 2e-4,
                                        "mean_abs_diff": 2e-5}}}}
ROWS = 3
N_KDA, N_MLA, N_MOE = 5, 1, 5


@pytest.fixture(scope="module")
def ref_mod():
    spec = importlib.util.spec_from_file_location(
        "ling3_reference", os.path.join(BENCH, "archs", "bailing_hybrid",
                                        "reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def arch():
    from benchmarks.lib.registry import arch_of
    return arch_of(C, BENCH)


@pytest.fixture(scope="module")
def model(arch):
    """The seeded weights as they are drawn, but for the norms' gains,
    which are bumped so a missing one shows."""
    cfg = arch.build(C, C["serving"]["engine"]["max_seq"], remat=False)
    params = arch.init(cfg, jax.random.PRNGKey(7), jnp.float32)
    bump = iter(jax.random.split(jax.random.PRNGKey(8), 64))

    def bumped(path, w):
        if path[-1].key in ("ln1", "ln2", "ln_f", "kv_norm", "o_norm"):
            return w + 0.1 * jax.random.normal(next(bump), w.shape)
        return w
    return cfg, jax.tree_util.tree_map_with_path(bumped, params)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        1, C["vocab_size"], size=n).astype(np.int32)


class Driver:
    """The engine's two jitted programs over one cache, driven by hand
    as the engine's admission and tick do."""

    def __init__(self, cfg, params, psz, chunk, pages=48, nblk=16):
        self.cfg, self.params, self.psz, self.chunk = cfg, params, psz, chunk
        self.cache = decode.init_paged_cache(cfg, pages + 1, psz, ROWS)
        self.bt = np.zeros((ROWS, nblk), np.int32)
        self.pos = np.zeros((ROWS,), np.int32)
        self.tok = np.zeros((ROWS,), np.int32)
        self.next_page = 1

    def admit(self, slot, toks, total):
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
    rows = [drv.admit(slot, toks[:n_prompt], len(toks))]
    for t in toks[n_prompt:]:
        rows.append(drv.tick({slot: t})[slot][None])
    return np.concatenate(rows)


# ------------------------------------ the engine's programs = one forward

CASES = {
    # page, chunk, prompt, ticks
    "chunk-32-padded-last": (16, 32, 75, 10),   # two whole chunks + 11 / 32
    "chunk-128-two-walks": (16, 128, 140, 6),   # 2 x 64 inside a call
    "whole-chunks": (16, 32, 64, 6),
    "short-prompt": (16, 32, 5, 12),            # one padded chunk
}


@pytest.mark.parametrize("case", list(CASES))
def test_prefill_chunks_then_ticks_are_one_reference_forward(
        model, arch, case, tick_attention):
    """Every position's logits against the reference's token-by-token
    forward: a chunk starts from the state and the tails the chunk
    before it left (zeros at 0) and moves them by its real tokens only,
    a tick from the last chunk's or the last tick's, in every KDA layer;
    the MLA layer expands in chunks and absorbs in ticks; and the
    program's counters are the reference's routing and its mean decay."""
    cfg, params = model
    psz, chunk, n_prompt, n_decode = CASES[case]
    drv = Driver(cfg, params, psz, chunk)
    toks = _tokens(n_prompt + n_decode, seed=len(case))
    got = _one_sequence(drv, 1, toks, n_prompt)
    want, routes, decay = arch.reference(params, jnp.asarray(toks), C,
                                         with_routes=True)
    assert np.asarray(want).std() > 0.05
    np.testing.assert_allclose(got, np.asarray(want), atol=5e-5)
    counts = bh.read_counters(drv.cache, cfg)
    routes = np.asarray(routes)
    assert counts["pairs_routed"] == routes.size == len(toks) * 4 * N_MOE
    assert counts["pairs_local"] == int((routes < HELD).sum())
    assert counts["kda_decay_count"] == len(toks) * N_KDA
    mean = counts["kda_decay_mass"] / counts["kda_decay_count"]
    assert mean == pytest.approx(float(decay), abs=2e-3)
    assert np.exp(-5) < mean < 0.999           # a live gate
    assert counts["kda_rows_live"] == n_decode * N_KDA
    assert counts["kda_rows_stepped"] == n_decode * N_KDA * ROWS
    # the chunks' delta rule: the prompt's tokens are the real ones, and
    # what was walked lies between them and the padded calls
    assert counts["kda_chunk_tokens_real"] == n_prompt * N_KDA
    assert n_prompt * N_KDA <= counts["kda_chunk_tokens_walked"] \
        <= -(-n_prompt // chunk) * chunk * N_KDA


def test_two_rows_in_one_tick_and_a_slot_that_changes_hands(model, arch):
    """Rows 0 and 2 decode at different depths in the same ticks; row
    2's sequence ends and a SHORTER one is admitted into its slot while
    row 0 goes on: the chunk at 0 zeroes the slot's state and tail, a
    tick leaves the row that is being filled alone, so the new
    sequence's logits are a fresh engine's."""
    cfg, params = model
    drv = Driver(cfg, params, 16, 32)
    a, b, c2 = _tokens(70, seed=1), _tokens(50, seed=2), _tokens(21, seed=3)
    got_a = [drv.admit(0, a[:40], len(a))]
    got_b = [drv.admit(2, b[:37], len(b))]
    for i in range(13):
        out = drv.tick({0: a[40 + i], 2: b[37 + i]})
        got_a.append(out[0][None])
        got_b.append(out[2][None])
    drv.leave(2)
    got_c = [drv.admit(2, c2[:5], len(c2))]
    row, drv.bt[2], drv.pos[2] = drv.bt[2].copy(), 0, 0   # not yet active
    state = np.asarray(drv.cache["kda"][:, 2])
    out = drv.tick({0: a[53]})
    got_a.append(out[0][None])
    assert (np.asarray(drv.cache["kda"][:, 2]) == state).all()
    drv.bt[2], drv.pos[2] = row, 5
    for i in range(16):
        out = drv.tick({0: a[54 + i], 2: c2[5 + i]})
        got_a.append(out[0][None])
        got_c.append(out[2][None])
    for got, toks in ((got_a, a), (got_b, b), (got_c, c2)):
        want = arch.reference(params, jnp.asarray(toks), C)
        np.testing.assert_allclose(np.concatenate(got), np.asarray(want),
                                   atol=5e-5)
    fresh = _one_sequence(Driver(cfg, params, 16, 32), 2, c2, 5)
    np.testing.assert_allclose(np.concatenate(got_c), fresh, atol=1e-6)


# ------------------------------------------------------------ the controls

CONTROLS = {"decay dropped": {"_no_decay": True},
            "delta term dropped": {"_no_delta": True},
            "beta 1": {"_beta_one": True},
            "state not carried": {"_state_reset_every": 32},
            "tails not carried": {"_tail_reset_every": 32},
            "l2 norms dropped": {"_no_l2norm": True},
            "output gate dropped": {"_no_out_gate": True},
            "a bfloat16 state": {"_state_dtype": "bfloat16"},
            "router bias dropped": {"_no_router_bias": True},
            "group limit off": {"_no_group_limit": True},
            "top-3 for top-4": {"_top_k": 3},
            "2.5 dropped": {"_routed_scale": 1.0},
            "head-wise gate dropped": {"_no_head_gate": True},
            "RoPE dropped": {"_no_rope": True}}


@pytest.fixture(scope="module")
def served_logits(model):
    cfg, params = model
    toks = _tokens(70, seed=11)
    return toks, _one_sequence(Driver(cfg, params, 16, 32), 0, toks, 64)


@pytest.mark.parametrize("control", list(CONTROLS) + ["float8 matmuls"])
def test_each_control_is_another_model(model, arch, ref_mod, served_logits,
                                       control):
    """The reference with one mechanism changed is far from the served
    logits, which sit on the unchanged reference: every mechanism is
    live under the seeded weights."""
    cfg, params = model
    toks, got = served_logits
    if control == "float8 matmuls":
        wrong = arch.reference(params, jnp.asarray(toks), C,
                               round_to="float8_e4m3fn")
    else:
        wrong = arch.reference(params, jnp.asarray(toks),
                               dict(C, **CONTROLS[control]))
    assert np.abs(got - np.asarray(wrong)).max() > 2e-3, control
    assert all(key in ref_mod.SWITCHES
               for switches in CONTROLS.values() for key in switches)


# ------------------------------------------------- the share, the router

def test_eight_shares_and_the_shared_expert_once_are_the_uncut_layer(
        model, ref_mod):
    """What each of the 8 shares of an expert layer computes of its
    routed part (4 held experts of 32, the router scoring all 32), added
    up with the shared expert once, is the layer with every expert held:
    in the reference, and in the program's `routed_experts` share by
    share."""
    cfg, params = model
    f32 = jnp.float32
    D_, F = C["hidden_size"], C["moe_intermediate_size"]
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    draw = lambda k, *s: 0.2 * jax.random.normal(k, s, f32)  # noqa: E731
    whole = {"w_gate": draw(ks[0], E_ALL, D_, F),
             "w_up": draw(ks[1], E_ALL, D_, F),
             "w_down": draw(ks[2], E_ALL, F, D_)}
    lp = dict(params["layers"][1], experts=whole)
    h = jax.random.normal(ks[3], (24, D_), f32)
    uncut = dict(C, num_experts=E_ALL, expert_offset=0)
    want = ref_mod.moe(h, lp, uncut)
    share0 = dict(lp, experts=jax.tree_util.tree_map(lambda w: w[:HELD],
                                                     whole))
    parts = ref_mod.moe(h, share0, C) \
        - ref_mod.moe(h, share0, C, with_shared=False)      # shared alone
    ids, weights = bh.route(lp["router"], lp["router_bias"], h, cfg)
    live = jnp.ones((24,), bool)
    program = jnp.zeros_like(want)
    for share in range(8):
        held = jax.tree_util.tree_map(
            lambda w: w[share * HELD:(share + 1) * HELD], whole)
        parts = parts + ref_mod.moe(
            h, dict(lp, experts=held), dict(C, expert_offset=share * HELD),
            with_shared=False)
        routed, sizes = bh._ds.routed_experts(
            held, h, ids, weights, live,
            dataclasses.replace(cfg, expert_offset=share * HELD))
        program = program + routed
    np.testing.assert_allclose(np.asarray(parts), np.asarray(want),
                               atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(program),
        np.asarray(ref_mod.moe(h, lp, uncut, with_shared=False)), atol=2e-4)


def test_the_router_by_hand():
    """8 experts in 4 groups of 2, the best 2 groups kept, top-2: the
    bias moves the choice and not the weight, and a token whose best
    expert lies outside the kept groups does not get it."""
    cfg = bh.BailingHybridConfig(
        max_seq=16, n_routed_experts=8, n_group=4, topk_group=2, top_k=2,
        d_model=8, routed_scaling_factor=2.5)
    logit = lambda p: float(np.log(p / (1 - p)))             # noqa: E731
    # scores by expert:    g0        g1        g2        g3
    scores = np.array([[0.9, 0.1, 0.5, 0.5, 0.6, 0.55, 0.2, 0.2]] * 2)
    # h: one-hot rows, each picks its row of logits out of W
    h = jnp.eye(8)[:2] * 1.0
    W = np.zeros((8, 8))
    W[:2] = np.vectorize(logit)(scores)
    ids0, w0 = bh.route(jnp.asarray(W), jnp.zeros(8), h, cfg)
    # group sums of the two largest: g0 1.0, g1 1.0, g2 1.15, g3 0.4 ->
    # g2 and (first of the tie) g0 kept; the best expert overall (0.9,
    # in g0) and 0.6 chosen; g1's 0.5s never
    assert sorted(np.asarray(ids0[0]).tolist()) == [0, 4]
    np.testing.assert_allclose(np.asarray(w0[0]).sum(), 2.5, rtol=1e-6)
    np.testing.assert_allclose(
        sorted(np.asarray(w0[0]).tolist()),
        [2.5 * 0.6 / 1.5, 2.5 * 0.9 / 1.5], rtol=1e-5)
    # a bias on expert 5 (+0.1: 0.65 > 0.6) moves the CHOICE to it; its
    # WEIGHT is its unbiased score 0.55
    bias = jnp.zeros(8).at[5].set(0.1)
    ids1, w1 = bh.route(jnp.asarray(W), bias, h, cfg)
    assert sorted(np.asarray(ids1[0]).tolist()) == [0, 5]
    np.testing.assert_allclose(
        sorted(np.asarray(w1[0]).tolist()),
        [2.5 * 0.55 / 1.45, 2.5 * 0.9 / 1.45], rtol=1e-5)
    # a bias that sinks g0 (its sum 0.9 + 0.1 - 0.6 = 0.4 + ...) takes the
    # token's best expert away: expert 0 scores 0.9 and is not chosen
    sink = jnp.zeros(8).at[0].set(-0.35).at[1].set(-0.35)
    ids2, _ = bh.route(jnp.asarray(W), sink, h, cfg)
    assert 0 not in np.asarray(ids2[0]).tolist()
    assert sorted(np.asarray(ids2[0]).tolist()) == [4, 5]


# ------------------------------------------------- the files of the cell

def _real_config():
    with open(os.path.join(BENCH, "configs",
                           "ling-3.0-flash-ep8-d6.json")) as f:
        return json.load(f)


def test_the_yardstick_counts_what_the_program_holds(arch):
    """`costs.weight_bytes`, `kv_bytes_per_token` and
    `state_bytes_per_row` against the program's own shapes at the
    configuration's sizes (no array is made), the ISSUE's arithmetic,
    and the tick's least time."""
    c = _real_config()
    e = c["serving"]["engine"]
    cfg = arch.build(c, e["max_seq"], remat=False)
    assert cfg.kinds == ("kda", "kda", "kda", "kda", "mla", "kda")
    params = jax.eval_shape(
        lambda: arch.init(cfg, jax.random.PRNGKey(0), cfg.dtype))
    held = sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(params))
    assert arch.weight_bytes(c) == held
    assert 4.83e9 < held < 4.87e9
    assert arch.total_params(c) == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    cache = jax.eval_shape(lambda: decode.init_paged_cache(
        cfg, e["kv_pages"] + 1, e["page_size"], e["num_slots"]))
    assert cache["lat"].shape == (1, 16385, 64, 640)
    assert cache["kda"].shape == (5, 256, 32, 128, 128)
    assert cache["kda"].dtype == jnp.float32
    assert cache["conv"].shape == (5, 256, 3 * 12288)
    rows = sum(cache[k].size * cache[k].dtype.itemsize
               for k in ("kda", "conv"))
    assert arch.state_bytes_per_row(c) * 256 == rows
    assert arch.kv_bytes_per_token(c) * 64 * 16385 \
        == cache["lat"].size * 2
    tick = arch.decode_tick(c, 256, 256 * 2300)
    assert arch.kda_step(c, 256)["bytes"] > 0.45 * tick["bytes"]
    assert 13.0e-3 < tick["bytes"] / 819e9 < 14.5e-3
    assert 0.97 < arch.experts_touched(c, 256) / 64 < 0.99


def test_the_configuration_file_holds_the_catalogs_numbers():
    c = _real_config()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r.get("name") == "Ling-3.0-flash")
    assert c["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if c.get(k) != v)
    assert differs == sorted(c["reduced"]) == sorted(c["published"])
    assert all(c["published"][k] == row["config"][k] for k in differs)
    for key in ("reduced_why", "stands_for", "resident_bytes", "assumed",
                "departures"):
        assert c[key], key


def test_the_reference_imports_jax_alone():
    path = os.path.join(BENCH, "archs", "bailing_hybrid", "reference.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add((node.module or "").split(".")[0])
    assert names <= {"jax", "__future__", "math"}, names


def test_the_new_cells_files_load_through_the_registry():
    from benchmarks.lib import traffic
    from benchmarks.lib.registry import Registry
    reg = Registry(REPO)
    cell = reg.cell("ling3-longtail")
    assert cell["config"] == "ling-3.0-flash-ep8-d6" and cell["chips"] == 1
    mix = reg.traffic(cell["traffic"])
    assert mix["clients"] == 2 * reg.config(cell["config"])[
        "serving"]["engine"]["num_slots"] == 512
    plan = traffic.schedule(mix, 5, 45.0, 2)
    lens = sorted(r["prompt_len"] for r in plan[:256])
    assert lens[0] == 128 and lens[-1] == 16384 and len(plan) == 512
    assert sum(n > 8192 for n in lens) == 5
    names = {m["name"] for m in reg.metrics_for("ling3-longtail",
                                                "per_layer")}
    assert {"kda_decay_mean.tput", "kda_rows_stepped_ratio.tput",
            "kda_step_roofline.tput", "kda_chunk_walked_ratio.tput",
            "row_state_gb.tput", "paged_tick_roofline.tput"} <= names
    decay = reg.metric("kda_decay_mean.tput")
    obs = {"stats0": {"kda_decay_mass": 10.0, "kda_decay_count": 20},
           "stats1": {"kda_decay_mass": 100.0, "kda_decay_count": 120}}
    assert reg.reader(decay["reader"])(obs, **decay["args"]) \
        == pytest.approx(0.9)
    assert reg.reader(decay["reader"])(
        {"stats0": {}, "stats1": {}}, **decay["args"]) is None
    # (PR 64) the tokens the chunks' delta rule walked over the real ones:
    # read from two stats dictionaries, null for a program without them
    walked = reg.metric("kda_chunk_walked_ratio.tput")
    obs = {"stats0": {"kda_chunk_tokens_walked": 2560,
                      "kda_chunk_tokens_real": 2000},
           "stats1": {"kda_chunk_tokens_walked": 2560 + 5 * 512,
                      "kda_chunk_tokens_real": 2000 + 5 * 450}}
    assert reg.reader(walked["reader"])(obs, **walked["args"]) \
        == pytest.approx(512 / 450)
    assert reg.reader(walked["reader"])(
        {"stats0": {"kda_rows_live": 1}, "stats1": {"kda_rows_live": 2}},
        **walked["args"]) is None


@pytest.mark.parametrize("held,want", [
    (5, "share"), (4, None), (0, None)])
def test_kda_step_roofline_reads_the_kernels_own_instructions(arch, held,
                                                              want):
    """`readers/roofline_kernel.py` on a hand-made reduction: the step
    kernel's five instructions (one a KDA layer) among the ten heaviest
    give the least time of the window's mean live rows over their summed
    time; with one of the five missing the time would leave part of the
    work out and nothing is returned; a program with no such kernel (a
    parent commit) returns nothing and does not raise."""
    from benchmarks.lib.costs import min_time
    from benchmarks.lib.peaks import peaks_for
    from benchmarks.lib.registry import Registry

    class Req:
        def __init__(self, times):
            self.token_times = times
    reg = Registry(REPO)
    c = _real_config()
    spec = reg.metric("kda_step_roofline.tput")
    ops = [[f"jit__paged_tick/kda_step.{i}", 0.0150] for i in range(held)] \
        + [["jit__paged_tick/fusion.7", 0.02],
           ["jit__prefill_chunk/kda_step.9", 0.5]]
    # 10 ticks in the traced second; 200 live rows each (token 0 of a
    # request is a prefill's, not a tick's)
    reqs = [Req([99.0] + [100.05 + 0.1 * t for t in range(10)])
            for _ in range(200)]
    obs = {"arch": arch, "config": c, "requests": reqs,
           "trace_t0": 100.0, "trace_t1": 101.0,
           "replica_info": {"kind": "TPU v5 lite"},
           "trace": {"programs": {"jit__paged_tick": [0.02] * 10},
                     "breakdown": {"device_ops": ops}}}
    got = reg.reader(spec["reader"])(obs, **spec["args"])
    if want is None:
        assert got is None
        return
    least = min_time(arch.kda_step(c, 200.0), peaks_for("TPU v5 lite"))
    assert got == pytest.approx(100 * least["seconds"] * 10 / (5 * 0.0150))
    assert 50 < got < 100
    assert obs["notes"]["kda_step_instances_among_the_heaviest"] == [5, 5]


# ------------------------------------------------------------- guards

@pytest.fixture(scope="module")
def served(model):
    cfg, params = model
    eng = GenerationEngine(params, cfg, num_slots=ROWS, page_size=16,
                           prefill_chunk=32, kv_pages=48,
                           enable_prefix_cache=False)
    yield eng
    eng.stop()


@pytest.mark.parametrize("what", [
    "prefix_cache", "kv_tiering", "kv_export", "kv_import", "session",
    "migrate_local", "speculation", "prefill_chunk_pages",
    "prefill_chunk_sub_blocks"])
def test_what_cannot_carry_state_or_frame_a_latent_refuses_by_name(
        model, served, what):
    """The first body with latent pages AND row state: the prefix cache
    is refused for the state, whatever frames pages for both, each
    naming what is missing."""
    cfg, params = model
    kw = dict(num_slots=ROWS, page_size=16, prefill_chunk=32,
              enable_prefix_cache=False)
    state = "per-row recurrent state .BailingHybridConfig."
    both = state + ".*latent page"
    if what == "prefix_cache":
        with pytest.raises(NotImplementedError, match=state):
            GenerationEngine(params, cfg, **dict(kw, enable_prefix_cache=True))
    elif what == "kv_tiering":
        with pytest.raises(NotImplementedError, match=both):
            GenerationEngine(params, cfg, kv_tiering=True, **kw)
    elif what == "kv_export":
        with pytest.raises(NotImplementedError, match=both):
            served.kv_export([1, 2, 3])
    elif what == "kv_import":
        with pytest.raises(NotImplementedError, match=both):
            served.kv_import([1, 2, 3], np.zeros(1), np.zeros(1))
    elif what == "session":
        with pytest.raises(NotImplementedError, match=both):
            served.submit([1, 2, 3], max_new_tokens=2, session_id="s")
    elif what == "migrate_local":
        with pytest.raises(NotImplementedError, match=both):
            kv_transfer.migrate_local(served, served, [1, 2, 3])
    elif what == "speculation":
        with pytest.raises(NotImplementedError, match="rolled back"):
            GenerationEngine(params, cfg, speculate_k=2, **kw)
    elif what == "prefill_chunk_pages":
        with pytest.raises(ValueError, match="whole latent pages"):
            GenerationEngine(params, cfg, **dict(kw, prefill_chunk=24))
    else:
        with pytest.raises(ValueError, match="sub-blocks"):
            GenerationEngine(params, cfg, **dict(kw, page_size=8,
                                                 prefill_chunk=72))


def test_the_engine_serves_it_and_counts(model, served, arch):
    """Five requests on three rows (slots change hands): greedy tokens
    equal the reference's argmax chain, and the engine's counters are
    the reference's routing, its decay, the state's resets and bytes."""
    cfg, params = model
    before = served.stats().to_dict()
    prompts = [_tokens(n, seed=n).tolist() for n in (41, 5, 70, 17, 33)]
    outs = [s.result(timeout=300) for s in
            [served.submit(p, max_new_tokens=9) for p in prompts]]
    local = context = 0
    for p, out in zip(prompts, outs):
        seq = jnp.asarray(list(p) + out[:8], jnp.int32)
        logits, routes, _ = arch.reference(params, seq, C, with_routes=True)
        assert out == np.asarray(logits)[len(p) - 1:].argmax(-1).tolist()
        local += int((np.asarray(routes) < HELD).sum())
        for pos in range(len(p), len(p) + 8):      # the ticks' positions
            context += (pos + 1) * N_MLA
    gain = {k: v - before[k] for k, v in served.stats().to_dict().items()
            if isinstance(v, (int, float))}
    ran = sum(len(p) + 8 for p in prompts)
    assert gain["moe_pairs_routed"] == ran * 4 * N_MOE
    assert gain["moe_pairs_local"] == local
    assert gain["kda_decay_count"] == ran * N_KDA
    assert np.exp(-5) < gain["kda_decay_mass"] / gain["kda_decay_count"] < 1
    assert gain["kda_rows_live"] == 5 * 8 * N_KDA
    assert gain["kda_rows_stepped"] >= gain["kda_rows_live"]
    assert gain["kda_chunk_tokens_real"] == gain["prefill_tokens"] * N_KDA \
        == sum(map(len, prompts)) * N_KDA
    assert gain["kda_chunk_tokens_real"] <= gain["kda_chunk_tokens_walked"] \
        <= (gain["prefill_tokens"] + gain["prefill_pad_tokens"]) * N_KDA
    assert gain["attn_keys_context"] == gain["attn_keys_attended"] \
        == gain["attn_keys_resident"] == gain["attn_keys_resident_paged"] \
        == context
    assert gain["attn_keys_gathered_paged"] == gain["attn_keys_gathered"] \
        >= context
    assert gain["state_resets"] == 5
    assert served.stats().row_state_bytes == sum(
        int(served._cache[k].nbytes) for k in ("kda", "conv"))


# ------------------------------------- the toy configuration as a cell

def test_the_toy_configuration_is_served_to_correct(tmp_path):
    """A temporary benchmark root gets a configuration that names
    `bailing_hybrid`, a mix at toy size and a cell; the benchmark's own
    run serves it and its check (64 + 6 positions: two whole chunks, six
    ticks) comes out correct."""
    sys.path.insert(0, os.path.join(BENCH, "tests"))
    try:
        import toy
    finally:
        sys.path.pop(0)
    from benchmarks import run as bench_run
    from benchmarks.lib.registry import Registry

    root = toy.build(str(tmp_path))
    b = os.path.join(root, "bm")
    with open(os.path.join(b, "configs", "toy-ling3.json"), "w") as f:
        json.dump(C, f)
    with open(os.path.join(b, "traffic", "longtail-toy.json"), "w") as f:
        json.dump({"kind": "serve", "loop": "closed", "clients": 6,
                   "block": 3, "blocks": 64, "warmup_first_tokens": 3,
                   "prompt_len": {"dist": "lognormal", "median": 40,
                                  "sigma": 0.5, "min": 8, "max": 120},
                   "output_len": {"dist": "fixed", "value": 10},
                   "trace_seconds": 1}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "toy-ling3", "source": "none",
                            "file": "bm/configs/toy-ling3.json",
                            "reduced": [], "why": "toy"})
    spec["workloads"].append({"name": "ling3-toy", "config": "toy-ling3",
                              "traffic": "longtail-toy", "chips": 1,
                              "why": "toy"})
    for m in spec["end_to_end"]:
        if m["name"] == "out_tok_per_s":
            m["workloads"].append("ling3-toy")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    reg = Registry(root)
    lines = []
    out = bench_run.run_cell(reg, "ling3-toy", seed=2**31 + 63,
                             seconds=4.0, trace=False, platform="cpu",
                             init_kwargs={"num_cpus": 6}, emit=lines.append)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"out_tok_per_s", "setup_s"}
    check = json.loads(lines[0])["check"]
    assert check["finite"] and check["positions"] == 70
    assert check["max_abs_diff"] <= 2e-4 and check["argmax_equal"] == 70
