"""ZAYA1 (`zaya`) at toy widths on the CPU, seeded weights whose taps,
temperature, residual scales and router all move the logits: the
engine's own two programs (chunks, then ticks that carry the tails)
against one forward of the plain reference at sizes that keep every
ratio (8 query heads on 2 key-value heads, a latent half the stream's
width, RoPE on half a head, 16 experts top-1, a router narrower than the
model, 4 layers so that the router's state crosses three), a prompt
split at every boundary, two rows in one tick and a row that changes
hands, the controls a comparison must catch, what the engine reports of
pages and tails, the refusals by name, the benchmark's architecture
files against the program, and the toy configuration served to
`correct`."""

import ast
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import decode, zaya
from ray_tpu.serve.llm import engine as engine_mod
from ray_tpu.serve.llm import kv_transfer
from ray_tpu.serve.llm.engine import GenerationEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
ARCH_DIR = os.path.join(BENCH, "archs", "zaya")
if REPO not in sys.path:
    sys.path.insert(0, REPO)

L, HEADS, E, D = 4, 8, 16, 128
C = {
    "name": "toy-zaya", "arch": "zaya", "attention_bias": False,
    "cca_time0": 2, "cca_time1": 2, "head_dim": 8, "hidden_act": "silu",
    "hidden_size": D, "layer_types": ["hybrid"] * 8, "lm_head_bias": False,
    "max_position_embeddings": 4096, "model_type": "zaya",
    "moe_intermediate_size": 32, "num_attention_heads": HEADS,
    "num_experts": E, "num_experts_per_tok": 1, "num_hidden_layers": L,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.5,
    "rms_norm_eps": 1e-5,
    "rope_parameters": {
        "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                   "rope_type": "default"},
        "rope_type": "default"},
    "router_hidden_size": 16, "sliding_window": None,
    "tie_word_embeddings": True, "vocab_size": 128,
    "torch_dtype": "float32",
    "published": {"num_hidden_layers": 8},
    "serving": {"engine": {"num_slots": 3, "max_seq": 128, "page_size": 4,
                           "kv_pages": 96, "prefill_chunk": 12,
                           "enable_prefix_cache": False},
                "check": {"prompt_len": 36, "decode_tokens": 10,
                          "tolerance": {"max_abs_diff": 1e-4,
                                        "mean_abs_diff": 1e-5}}}}
ROWS = 3
LATENT = (HEADS + 2) * 8


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "zaya_" + name, os.path.join(ARCH_DIR, name + ".py"))
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
    """The seeded weights as they are drawn, but for the norms' gains
    and the balancing bias, which are bumped so a missing one shows."""
    cfg = arch.build(C, C["serving"]["engine"]["max_seq"], remat=False)
    params = arch.init(cfg, jax.random.PRNGKey(7), jnp.float32)
    bump = iter(jax.random.split(jax.random.PRNGKey(8), 64))

    def bumped(path, w):
        if path[-1].key in ("ln1", "ln2", "ln_f", "ln", "beta"):
            return w + 0.1 * jax.random.normal(next(bump), w.shape)
        return w
    return cfg, jax.tree_util.tree_map_with_path(bumped, params)


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
    # page, chunk, prompt, ticks: three whole chunks and a padded fourth
    # (its pad leaves no tail and is routed nowhere), then 20 ticks
    "chunk-12-page-4": (4, 12, 41, 20),
    "chunk-16-page-8": (8, 16, 53, 12),
    "whole-chunks": (4, 12, 36, 9),
    # one padded chunk: the ticks start from the tails of its 5th token
    "short-prompt": (4, 12, 5, 14),
}


@pytest.mark.parametrize("case", list(CASES))
def test_prefill_chunks_then_ticks_are_one_reference_forward(
        model, reference, case, tick_attention):
    """Every position's logits against the reference's full-mask
    forward: a chunk starts from the tails the chunk before it left
    (zeros at 0), a tick from the last chunk's or the last tick's, in
    all three tails of every layer; the router's state crosses the
    layers; and the program's own counters are the reference's routing
    and its mean chosen weight."""
    cfg, params = model
    psz, chunk, n_prompt, n_decode = CASES[case]
    drv = Driver(cfg, params, psz, chunk)
    toks = _tokens(n_prompt + n_decode, seed=len(case))
    got = _one_sequence(drv, 1, toks, n_prompt)
    want, routes, gates = reference.forward(params, jnp.asarray(toks), C,
                                            with_routes=True)
    assert np.asarray(want).std() > 0.05
    np.testing.assert_allclose(got, np.asarray(want), atol=3e-5)
    counts = zaya.read_counters(drv.cache, cfg)
    assert counts["pairs_routed"] == counts["pairs_local"] \
        == np.asarray(routes).size == len(toks) * 1 * L
    assert counts["gate_tokens"] == len(toks) * L
    assert counts["experts_held"] == n_decode * L * E
    mean = counts["gate_mass"] / counts["gate_tokens"]
    assert mean == pytest.approx(float(np.asarray(gates).mean()), abs=2e-3)
    assert 1 / E < mean < 0.9          # a live router, not renormalised


def test_a_prompt_split_at_every_boundary_is_the_same_sequence(model,
                                                               reference):
    """26 tokens, the first n through chunks of one page (4) and the
    rest through ticks, for every n: wherever the chunks end and the
    ticks begin (a whole last chunk or a padded one), the tails cross
    the boundary and the logits are the reference's."""
    cfg, params = model
    toks = _tokens(26, seed=4)
    want = np.asarray(reference.forward(params, jnp.asarray(toks), C))
    for n_prompt in range(1, 26):
        got = _one_sequence(Driver(cfg, params, 4, 4), n_prompt % ROWS,
                            toks, n_prompt)
        np.testing.assert_allclose(got, want, atol=3e-5,
                                   err_msg=str(n_prompt))


def test_two_rows_in_one_tick_and_a_slot_that_changes_hands(model,
                                                            reference):
    """Rows 0 and 2 decode at different depths in the same ticks; row 2's
    sequence ends and a SHORTER one (5 tokens, one padded chunk) is
    admitted into its slot while row 0 goes on: what the earlier sequence
    left in the slot's tails is never read (nothing zeroes them: a chunk
    at 0 reads none, and a tick leaves the tails of a row that is being
    filled alone), so the new sequence's logits are a fresh engine's."""
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
        want = reference.forward(params, jnp.asarray(toks), C)
        np.testing.assert_allclose(np.concatenate(got), np.asarray(want),
                                   atol=3e-5)
    fresh = _one_sequence(Driver(cfg, params, 4, 12), 2, c2, 5)
    np.testing.assert_allclose(np.concatenate(got_c), fresh, atol=1e-6)


# ------------------------------------------------------------ the controls

CONTROLS = {"both convolutions left out": {"_no_conv": True},
            "second convolution depthwise": {"_conv1_depthwise": True},
            "q-k mean left out": {"_no_mean": True},
            "late value from this token": {"_v_now": True},
            "temperature 1": {"_tau_one": True},
            "rotary over the whole head": {"_rotary_dim": 8},
            "depth averaging left out": {"_no_carry": True},
            "two experts a token": {"_top_k": 2},
            "chosen weight 1": {"_gate_one": True},
            "residual scaling left out": {"_no_join": True}}


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
    """What tools/zaya_limits.py sets the cell's limits from: the
    reference computed wrong in one way is not what the program
    computes: each fails both limits of the toy cell's tolerance (1e-4
    and 1e-5, which the program itself meets thirtyfold) by a factor of
    five or more."""
    params = model[1]
    toks, got = served_logits
    kw = {"round_to": "float8_e4m3fn"} if control == "float8 matmuls" else {}
    wrong = np.asarray(reference.forward(
        params, jnp.asarray(toks), dict(C, **CONTROLS.get(control, {})),
        **kw))
    limit = C["serving"]["check"]["tolerance"]
    diff = np.abs(got - wrong)
    over = min(diff.max() / limit["max_abs_diff"],
               diff.mean() / limit["mean_abs_diff"])
    assert over > 5, (control, over)


# ------------------------------------------ what the engine reports

def _real_config():
    with open(os.path.join(BENCH, "configs", "zaya1-8b-pp2-d20.json")) as f:
        return json.load(f)


def test_a_page_is_k_and_v_of_every_layer_and_a_row_keeps_three_tails(
        arch, model):
    """The engine's pool bytes are pages x page_size x what a token
    occupies in every layer's k and v pages, and its row state is the
    three tail arrays; at the published widths a token is 20,480 B over
    the cut's 20 layers and a row's tails 107,520 B."""
    cfg, params = model
    eng = GenerationEngine(params, cfg, num_slots=ROWS, page_size=4,
                           prefill_chunk=12, kv_pages=32,
                           enable_prefix_cache=False)
    try:
        st = eng.stats()
        assert arch.kv_bytes_per_token(C) == L * 2 * 2 * 8 * 2
        # (the toy serves float32: twice the bytes of the served type)
        assert st.kv_pool_bytes == 32 * 4 * 2 * arch.kv_bytes_per_token(C)
        assert st.row_state_bytes \
            == 2 * ROWS * arch.row_state_bytes_per_row(C) \
            == sum(eng._cache[k].nbytes for k in ("cz", "cc", "cv"))
        assert eng._cache["cz"].shape == eng._cache["cc"].shape \
            == (L, ROWS, LATENT)
        assert eng._cache["cv"].shape == (L, ROWS, 8)
        for name in ("moe_gate_mass", "moe_gate_tokens", "moe_pairs_routed",
                     "moe_pairs_local", "moe_experts_touched",
                     "moe_experts_held", "moe_load_max"):
            assert name in st.to_dict(), name
    finally:
        eng.stop()
    c = _real_config()
    e = c["serving"]["engine"]
    real = arch.build(c, e["max_seq"], remat=False)
    shapes = jax.eval_shape(lambda: decode.init_paged_cache(
        real, e["kv_pages"] + 1, e["page_size"], e["num_slots"]))
    P, psz, B = e["kv_pages"] + 1, e["page_size"], e["num_slots"]
    assert shapes["k"].shape == shapes["v"].shape == (20, P, psz, 2 * 128)
    assert shapes["cz"].shape == shapes["cc"].shape == (20, B, 1280)
    assert shapes["cv"].shape == (20, B, 128)
    nbytes = lambda s: int(np.prod(s.shape)) * s.dtype.itemsize  # noqa: E731
    assert arch.kv_bytes_per_token(c) == real.token_bytes * 20 == 20_480
    assert nbytes(shapes["k"]) + nbytes(shapes["v"]) == P * psz * 20_480
    assert arch.row_state_bytes_per_row(c) == 20 * 2688 * 2 == 107_520
    assert sum(nbytes(shapes[k]) for k in ("cz", "cc", "cv")) == B * 107_520
    # rows, not pages, limit: a block of the mix fits the pool
    assert e["kv_pages"] * psz * 20_480 == pytest.approx(4.53e9, rel=0.01)


def test_costs_against_hand_counts(arch):
    c = _real_config()
    attention = 2048 * 1024 + 2048 * 256 + 2 * 2048 * 128 + 1024 * 2048
    conv = 2 * 10 * 128 * 128
    expert = 3 * 2048 * 2048
    router = 2048 * 256 + 2 * 256 * 256 + 256 * 16
    assert (attention, conv, expert) == (5_242_880, 327_680, 12_582_912)
    assert arch.attention_params(c) == attention
    assert arch.expert_params(c) == expert
    # ISSUE 55's table: 207.6 M a layer, 20 layers + 537.1 M of tied
    # embedding = 4.689 B = 9.38 GB at 2 B a parameter
    layer = attention + conv + 16 * expert + router
    assert arch.matmul_params(c) == 20 * layer + 262272 * 2048
    assert 20 * layer + 262272 * 2048 <= arch.total_params(c) \
        < 20 * layer + 262272 * 2048 + 600_000
    assert arch.total_params(c) == pytest.approx(4.689e9, rel=0.002)
    assert arch.weight_bytes(c) == pytest.approx(9.38e9, rel=0.005)
    # ...and what the arrays hold
    cfg = arch.build(c, c["serving"]["engine"]["max_seq"], remat=False)
    shapes = jax.eval_shape(
        lambda k: arch.init(cfg, k, cfg.dtype), jax.random.PRNGKey(0))
    held = sum(int(np.prod(s.shape)) * s.dtype.itemsize
               for s in jax.tree_util.tree_leaves(shapes))
    assert arch.weight_bytes(c) == pytest.approx(held, rel=0.005)
    assert arch.weight_bytes(c) == held
    # 96 rows choose 1 of 16 each: 15.97 of 16 get a token
    assert arch.experts_touched(c, 96) == pytest.approx(
        16 * (1 - (15 / 16) ** 96))
    assert 15.9 < arch.experts_touched(c, 96) < 16
    rows, ctx = 96, 96 * 1300
    tick = arch.decode_tick(c, rows, ctx)
    # experts 8.0 GB, pages 2.6 GB, the head 1.07 GB: ~12 GB, memory-bound
    assert tick["bytes"] == pytest.approx(12.0e9, rel=0.03)
    assert tick["flops"] / 197e12 < tick["bytes"] / 819e9
    parts = (arch.cca_mix(c, rows, rows), arch.attn_latent(c, rows, ctx),
             arch.moe_route(c, rows), arch.moe_experts(c, rows))
    assert sum(p["bytes"] for p in parts) < tick["bytes"] \
        < sum(p["bytes"] for p in parts) + 1.3e9     # + the head and W_o
    a = arch.attn_latent(c, rows, ctx)
    assert a["bytes"] == 20 * 1024 * (ctx + rows)
    assert a["flops"] == 20 * 2 * 8 * 256 * (ctx + rows)
    ac = arch.attn_latent_chunk(c, 512, 1024)
    assert ac["flops"] == 20 * 2 * 8 * 256 * 512 * (1024 + 513 / 2)
    assert ac["bytes"] == 20 * 1024 * (1024 + 512)
    m = arch.moe_experts(c, rows)
    assert m["flops"] == 20 * 2 * expert * rows            # top-1
    assert m["bytes"] == pytest.approx(
        20 * (arch.experts_touched(c, rows) * expert * 2
              + rows * 2 * 2048 * 2))
    assert arch.cca_mix(c, rows, rows)["flops"] \
        == 20 * 2 * rows * (2048 * 1536 + conv)
    chunk = arch.prefill_chunk(c, 512, 1024, with_head=False)
    head = arch.prefill_chunk(c, 512, 1024, with_head=True)
    assert head["bytes"] - chunk["bytes"] == 262272 * 2048 * 2
    assert head["flops"] - chunk["flops"] == 2 * 262272 * 2048   # one row
    assert chunk["bytes"] > 20 * 16 * expert * 2
    with pytest.raises(NotImplementedError, match="serves only"):
        arch.train_flops_per_token(c, 4096)


# ---------------------------------------------- the benchmark's files

def test_the_benchmarks_init_is_the_programs_and_shows_each_mechanism(
        arch, model, reference):
    cfg, _ = model
    key = jax.random.PRNGKey(11)
    ours = jax.jit(lambda k: arch.init(cfg, k, jnp.bfloat16))(key)
    theirs = jax.jit(lambda k: zaya.init_params(cfg, k, jnp.bfloat16))(key)
    assert jax.tree_util.tree_structure(ours) \
        == jax.tree_util.tree_structure(theirs)
    for a, b in zip(jax.tree_util.tree_leaves(ours),
                    jax.tree_util.tree_leaves(theirs)):
        assert a.dtype == b.dtype and bool((a == b).all())
    # layers are a tuple: each layer's experts are an array of their own;
    # layer 0 has no carry; the head is the embedding
    assert isinstance(ours["layers"], tuple) and "wlm" not in ours
    lp = ours["layers"][1]
    assert lp["experts"]["w_gate"].shape == (E, D, 32)
    assert lp["wqk"].shape == (D, HEADS + 2, 8)
    assert lp["w1"].shape == (2, HEADS + 2, 8, 8)
    assert "gamma" in lp["router"] and "gamma" not in ours["layers"][0][
        "router"]
    assert lp["router"]["wd"].dtype == jnp.float32
    assert float(jnp.abs(lp["router"]["beta"]).max()) == 0
    # every mechanism is drawn away from its neutral value
    assert 3 < float(lp["tau"].mean()) < 5
    assert 0.4 < float(jnp.abs(lp["w0"]).mean()) < 0.8
    assert 0.5 <= float(lp["router"]["gamma"].min()) \
        and float(lp["router"]["gamma"].max()) <= 1.0
    assert 0.05 < float(jnp.abs(lp["join1"][0] - 1).mean()) < 0.15
    assert 0.0005 < float(jnp.abs(lp["join1"][1]).mean()) < 0.003


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
        lambda name, *a: None if name == "ray_tpu.models.zaya"
        else real(name, *a))
    spec = importlib.util.spec_from_file_location(
        "zaya_arch_probe", os.path.join(ARCH_DIR, "__init__.py"),
        submodule_search_locations=[ARCH_DIR])
    mod = importlib.util.module_from_spec(spec)
    with pytest.raises(ImportError, match="ray_tpu.models.zaya"):
        spec.loader.exec_module(mod)


def test_no_other_configuration_imports_the_model():
    """Nothing this model brings runs at import or at replica start for
    another configuration: `ray_tpu.models` does not import it, nor do
    the engine, decode, or the modules whose functions it runs."""
    code = ("import sys; import ray_tpu.models, ray_tpu.serve.llm.engine; "
            "sys.path.insert(0, %r); "
            "from benchmarks.lib.registry import arch_of; arch_of({}); "
            "arch_of({'arch': 'exaone_moe'}); "
            "arch_of({'arch': 'deepseek_v2'}); "
            "import ray_tpu.models.exaone_moe; "
            "bad = [m for m in sys.modules if 'zaya' in m]; "
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
    row = next(r for r in rows if r["name"] == "ZAYA1-8B")
    c = _real_config()
    assert c["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if c.get(k) != v)
    assert differs == c["reduced"] == ["num_hidden_layers"]
    assert c["published"] == {"num_hidden_layers": 40}
    assert c["num_hidden_layers"] == 20 and len(c["layer_types"]) == 40
    assert "first of two v5e chips" in c["stands_for"]
    for key in ("assumed", "departures", "resident_bytes", "reduced_why"):
        assert c[key], key
    assert any("MoD" in d for d in c["departures"])
    assert any("tied head" in d for d in c["departures"])
    for point in ("convolutions", "q-k mean", "norm", "RoPE", "values",
                  "router", "depth averaging", "chosen expert's weight",
                  "residual scaling", "torch_dtype"):
        assert any(point in k for k in c["assumed"]), point
    cfg = arch.build(c, c["serving"]["engine"]["max_seq"], remat=False)
    assert cfg == zaya.ZayaConfig(
        max_seq=c["serving"]["engine"]["max_seq"], n_layers=20)
    assert (cfg.latent, cfg.token_bytes, cfg.experts_held) == (1280, 1024, 16)


def test_the_new_cells_files_load_through_the_registry():
    from benchmarks.lib.registry import Registry
    reg = Registry(REPO)
    cell = reg.cell("zaya-reason")
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("zaya1-8b-pp2-d20", "reason_short", 1)
    c, mix = reg.config(cell["config"]), reg.traffic(cell["traffic"])
    assert c["arch"] == "zaya"
    assert (mix["loop"], mix["clients"], mix["block"], mix["blocks"],
            mix["warmup_first_tokens"], mix["trace_seconds"]) \
        == ("closed", 192, 96, 16, 96, 6)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 512,
                                 "sigma": 0.6, "min": 128, "max": 4096}
    assert mix["output_len"] == {"dist": "lognormal", "median": 1024,
                                 "sigma": 0.4, "min": 384, "max": 3072}
    e = c["serving"]["engine"]
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] \
        <= e["max_seq"] == 7168
    assert mix["block"] == e["num_slots"] == 96
    assert e["max_queue_len"] >= mix["clients"]
    assert not e["enable_prefix_cache"]
    names = {m["name"] for m in reg.metrics_for("zaya-reason", "per_layer")}
    assert {"moe_gate_weight_mean.tput", "row_state_gb.tput",
            "experts_touched_share.tput", "paged_tick_roofline.tput",
            "prefill_chunk_roofline.tput", "replica_start_s"} <= names
    assert not {"kv_held_share.kx", "attn_sink_mass_share.tput"} & names
    assert {m["name"] for m in reg.metrics_for(
        "zaya-reason", "end_to_end")} == {"out_tok_per_s", "setup_s"}
    for name in names:
        spec = reg.metric(name)
        reg.reader(spec["reader"])
    gate = reg.metric("moe_gate_weight_mean.tput")
    obs = {"stats0": {"moe_gate_mass": 10.0, "moe_gate_tokens": 100},
           "stats1": {"moe_gate_mass": 50.0, "moe_gate_tokens": 200}}
    assert reg.reader(gate["reader"])(obs, **gate["args"]) == 0.4
    # a parent without the counters, or a model without them (they stay
    # 0), reads nothing, quietly
    assert reg.reader(gate["reader"])(
        {"stats0": {}, "stats1": {}}, **gate["args"]) is None
    still = {"moe_gate_mass": 0.0, "moe_gate_tokens": 0}
    assert reg.reader(gate["reader"])(
        {"stats0": still, "stats1": still}, **gate["args"]) is None


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
def test_what_cannot_carry_a_tail_refuses_by_name(model, served, what):
    cfg, params = model
    kw = dict(num_slots=ROWS, page_size=4, prefill_chunk=12,
              enable_prefix_cache=False)
    missing = "per-row recurrent state .ZayaConfig."
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
    reference's own routing (one pair a token and layer), and the mean
    chosen weight is the reference's."""
    cfg, params = model
    before = served.stats().to_dict()
    prompts = [_tokens(n, seed=n).tolist() for n in (41, 5, 30, 17, 22)]
    outs = [s.result(timeout=300) for s in
            [served.submit(p, max_new_tokens=14) for p in prompts]]
    mass = context = 0
    for p, out in zip(prompts, outs):
        seq = jnp.asarray(list(p) + out[:13], jnp.int32)
        logits, routes, gates = reference.forward(params, seq, C,
                                                  with_routes=True)
        assert out == np.asarray(logits)[len(p) - 1:].argmax(-1).tolist()
        mass += float(np.asarray(gates).sum())
        for pos in range(len(p), len(p) + 13):     # the ticks' positions
            context += (pos + 1) * L
    gain = {k: v - before[k] for k, v in served.stats().to_dict().items()
            if isinstance(v, (int, float))}
    ran = sum(len(p) + 13 for p in prompts)
    assert gain["moe_pairs_routed"] == gain["moe_pairs_local"] \
        == gain["moe_gate_tokens"] == ran * L
    assert gain["moe_gate_mass"] == pytest.approx(mass, rel=2e-3)
    assert gain["prefill_tokens"] == sum(map(len, prompts))
    assert gain["attn_keys_context"] == gain["attn_keys_attended"] \
        == gain["attn_keys_resident"] == context
    assert gain["attn_keys_gathered"] >= context
    assert gain["state_resets"] == 5 and gain["prefill_tokens_sparse"] == 0
    assert served.stats().row_state_bytes == sum(
        int(served._cache[k].nbytes)
        for k in decode.paged_body(cfg).row_state_keys)


# ------------------------------------- the toy configuration as a cell

def test_the_toy_configuration_is_served_to_correct(tmp_path):
    """A temporary benchmark root gets a configuration that names
    `zaya`, a reasoning mix at toy size and a cell; the benchmark's own
    run serves it and its check (36 + 10 positions: three chunks, ten
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
    with open(os.path.join(b, "configs", "toy-zaya.json"), "w") as f:
        json.dump(C, f)
    with open(os.path.join(b, "traffic", "reason-toy.json"), "w") as f:
        json.dump({"kind": "serve", "loop": "closed", "clients": 6,
                   "block": 3, "blocks": 64, "warmup_first_tokens": 3,
                   "prompt_len": {"dist": "lognormal", "median": 24,
                                  "sigma": 0.4, "min": 8, "max": 48},
                   "output_len": {"dist": "fixed", "value": 12},
                   "trace_seconds": 1}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "toy-zaya", "source": "none",
                            "file": "bm/configs/toy-zaya.json",
                            "reduced": [], "why": "toy"})
    spec["workloads"].append({"name": "zaya-toy", "config": "toy-zaya",
                              "traffic": "reason-toy", "chips": 1,
                              "why": "toy"})
    for m in spec["end_to_end"]:
        if m["name"] == "out_tok_per_s":
            m["workloads"].append("zaya-toy")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    reg = Registry(root)
    lines = []
    out = bench_run.run_cell(reg, "zaya-toy", seed=2**31 + 55,
                             seconds=4.0, trace=False, platform="cpu",
                             init_kwargs={"num_cpus": 6}, emit=lines.append)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"out_tok_per_s", "setup_s"}
    check = json.loads(lines[0])["check"]
    assert check["finite"] and check["positions"] == 46
    assert check["max_abs_diff"] <= 1e-4 and check["argmax_equal"] == 46
