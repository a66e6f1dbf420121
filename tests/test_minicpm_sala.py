"""MiniCPM-SALA at toy widths on the CPU, seeded weights: the chunked
lightning scan against the recurrence, the engine's own two programs
against one forward of the plain reference, the block selection against
the reference's, the benchmark's architecture files against the program,
the guards for everything that treats a page as a sequence's whole
state, and the toy configuration served to `correct` from a temporary
benchmark root."""

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
from ray_tpu.models import minicpm_sala as ms
from ray_tpu.serve.llm import engine as engine_mod
from ray_tpu.serve.llm import kv_transfer
from ray_tpu.serve.llm.engine import GenerationEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
ARCH_DIR = os.path.join(BENCH, "archs", "minicpm_sala")
if REPO not in sys.path:
    sys.path.insert(0, REPO)

A, L = ms.ATTN, ms.LIN
MIX = (A, L, L, A, L, A)
# The toy configuration, as a benchmark file would state it.
C = {
    "name": "toy-sala", "arch": "minicpm_sala", "hidden_size": 32,
    "intermediate_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "lightning_nh": 4,
    "lightning_nkv": 4, "lightning_head_dim": 8, "vocab_size": 128,
    "rope_theta": 10000.0, "scale_emb": 12, "scale_depth": 1.4,
    "dim_model_base": 8, "num_hidden_layers": 6, "layer_mixers": list(MIX),
    "published": {"num_hidden_layers": 12}, "torch_dtype": "float32",
    "sparse_config": {"block_size": 8, "kernel_size": 4, "kernel_stride": 2,
                      "init_blocks": 1, "window_size": 16, "topk": 4,
                      "dense_len": 32},
    "serving": {"engine": {"num_slots": 3, "max_seq": 128, "page_size": 8,
                           "kv_pages": 64, "prefill_chunk": 16,
                           "enable_prefix_cache": False},
                "check": {"prompt_len": 48, "decode_tokens": 4,
                          "tolerance": {"max_abs_diff": 1e-3,
                                        "mean_abs_diff": 1e-4}}}}
PSZ, CHUNK, NBLK, ROWS = 8, 16, 16, 3


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "sala_" + name, os.path.join(ARCH_DIR, name + ".py"))
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
    params = ms.init_params(cfg, jax.random.PRNGKey(7))
    # norms that are not all ones, so a missing one shows
    bump = iter(jax.random.split(jax.random.PRNGKey(8), 64))
    params = jax.tree_util.tree_map(
        lambda w: w if w.ndim > 2 or w.dtype != jnp.float32 or w.size > 4096
        else w + 0.1 * jax.random.normal(next(bump), w.shape), params)
    return cfg, params


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        1, C["vocab_size"], size=n).astype(np.int32)


def _prefill(params, cfg, cache, bt_row, toks, slot):
    """`toks` through engine._prefill_chunk chunk by chunk, as the
    engine's admission does; returns (logits of the real tokens, cache)."""
    rows = []
    for s in range(0, len(toks), CHUNK):
        real = toks[s:s + CHUNK]
        chunk = np.zeros((1, CHUNK), np.int32)
        chunk[0, :len(real)] = real
        logits, cache = engine_mod._prefill_chunk(
            params, jnp.asarray(chunk), jnp.int32(s), cache,
            jnp.asarray(bt_row[None]), cfg, slot=jnp.int32(slot),
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


# ---------------------------------------------------------------- kernels

@pytest.mark.parametrize("valid", [32, 21])
def test_the_chunked_scan_is_the_recurrence(model, valid):
    cfg, _ = model
    T, H, D = 32, cfg.lin_heads, cfg.lin_head_dim
    q, k, v = (jax.random.normal(key, (T, H, D))
               for key in jax.random.split(jax.random.PRNGKey(1), 3))
    S0 = jax.random.normal(jax.random.PRNGKey(2), (H, D, D))
    o, S = ms.lightning_chunked(q, k, v, S0, jnp.arange(T) < valid, cfg,
                                sub=8)
    lam = np.exp(-np.asarray(ms.lightning_slopes(H)))
    want_S, want_o = np.asarray(S0, np.float64), []
    for t in range(valid):
        want_S = lam[:, None, None] * want_S + np.einsum(
            "hd,he->hde", np.asarray(k[t]), np.asarray(v[t]))
        want_o.append(np.einsum("hd,hde->he", np.asarray(q[t]), want_S)
                      * D ** -0.5)
    np.testing.assert_allclose(np.asarray(o[:valid]), np.stack(want_o),
                               atol=2e-5)
    # a pad after the last real token neither decays nor adds
    np.testing.assert_allclose(np.asarray(S), want_S, atol=2e-5)


def test_selection_is_the_references(model, reference):
    """Same queries, same compressed keys: the program's chosen blocks
    are the reference's, as sets, on both kinds of position (a block
    that ends at the query, and one that does not)."""
    cfg, _ = model
    sp, G, R, Dh = C["sparse_config"], 2, 2, 8
    nb = 14
    q = jax.random.normal(jax.random.PRNGKey(3), (6, G, R, Dh))
    kbar = jax.random.normal(jax.random.PRNGKey(4), (nb * 4, G, Dh))
    qpos = jnp.asarray([32, 39, 57, 64, 95, 111])
    want = np.asarray(reference.chosen_blocks(q, kbar, qpos, sp))
    got = np.asarray(ms.select_blocks(
        q, jnp.moveaxis(kbar, 0, 1)[None], qpos, cfg))
    assert got.shape == want.shape == (6, G, sp["topk"])
    for n in range(6):
        for g in range(G):
            assert set(got[n, g]) == set(want[n, g]), (n, g)
            own = int(qpos[n]) // 8
            assert {0, own, own - 1} <= set(got[n, g])     # forced
            assert max(got[n, g]) <= own                   # causal


# ------------------------------------ the engine's programs = one forward

def _through_the_programs(case, cfg, params):
    """Returns (logits from the engine's two programs, the tokens they
    belong to) for one sequence, driven as `case` says."""
    cache = _fresh(cfg)
    bt = np.zeros((ROWS, NBLK), np.int32)
    pos = np.zeros((ROWS,), np.int32)
    tok = np.zeros((ROWS,), np.int32)
    n_prompt, n_decode, slot = {"below-dense_len": (24, 5, 0),
                                "across-dense_len": (77, 6, 1),
                                "slot-reused": (53, 4, 2),
                                "two-rows": (45, 6, 1)}[case]
    toks = _tokens(n_prompt + n_decode, seed=len(case))
    if case == "slot-reused":
        # an earlier request leaves its state and pages behind
        first = _tokens(40, seed=99)
        row = np.zeros((NBLK,), np.int32)
        row[:6] = np.arange(30, 36)
        _, cache = _prefill(params, cfg, cache, row, first, slot)
        bt[slot], pos[slot], tok[slot] = row, 40, 5
        for _ in range(3):
            _, cache = _tick(params, cfg, cache, bt, pos, tok)
            pos[slot] += 1
        bt[slot], pos[slot], tok[slot] = 0, 0, 0           # evicted
    if case == "two-rows":
        # another row decodes at another depth all the while
        other = _tokens(70, seed=5)
        row = np.zeros((NBLK,), np.int32)
        row[:10] = np.arange(20, 30)
        _, cache = _prefill(params, cfg, cache, row, other, 2)
        bt[2], pos[2], tok[2] = row, 70, 9
    row = np.zeros((NBLK,), np.int32)
    row[:12] = np.arange(3, 15)
    got, cache = _prefill(params, cfg, cache, row, toks[:n_prompt], slot)
    bt[slot] = row
    rows = [got]
    for i in range(n_decode):
        pos[slot], tok[slot] = n_prompt + i, toks[n_prompt + i]
        logits, cache = _tick(params, cfg, cache, bt, pos, tok)
        rows.append(logits[slot][None])
        if case == "two-rows":
            pos[2] += 1
    return np.concatenate(rows), toks


@pytest.mark.parametrize("case", ["below-dense_len", "across-dense_len",
                                  "slot-reused", "two-rows"])
def test_prefill_chunks_then_ticks_are_one_reference_forward(
        model, reference, case):
    """Chunks of 16 then ticks, through engine._prefill_chunk and
    engine._paged_tick: every position's logits against the reference's
    (toy dense_len 32: contexts on both sides of it; 77 and 53 leave a
    partial last chunk; a slot that an earlier request used; a second
    row at another depth)."""
    cfg, params = model
    got, toks = _through_the_programs(case, cfg, params)
    want = np.asarray(reference.forward(params, jnp.asarray(toks), C,
                                        query_block=16, width_blocks=2))
    assert want.std() > 0.05
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_a_dense_chunk_in_spans_is_the_same_attention(model, reference,
                                                      monkeypatch):
    """Spans of two pages instead of one span: the merged softmax parts
    are the softmax (the real sizes split 8,192 keys into two spans)."""
    cfg, params = model
    monkeypatch.setattr(ms, "_DENSE_SPAN_KEYS", 16)
    engine_mod._prefill_chunk.clear_cache()
    try:
        got, toks = _through_the_programs("below-dense_len", cfg, params)
    finally:
        engine_mod._prefill_chunk.clear_cache()
    want = np.asarray(reference.forward(params, jnp.asarray(toks), C,
                                        query_block=16))
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_selection_switched_off_is_another_model(model, reference):
    """The reference with `dense_len` out of reach attends to all it
    holds: past the toy dense_len that is not what the program computes,
    so the comparison above does see the selection."""
    cfg, params = model
    got, toks = _through_the_programs("across-dense_len", cfg, params)
    dense = dict(C, sparse_config=dict(C["sparse_config"], dense_len=10**6))
    want = np.asarray(reference.forward(params, jnp.asarray(toks), dense,
                                        query_block=16))
    diff = np.abs(got - want).max(axis=1)
    assert diff[:32].max() < 1e-6 and diff[40:].max() > 5e-5


def test_a_tick_leaves_idle_rows_state_alone(model):
    cfg, params = model
    cache = _fresh(cfg)
    cache["state"] = cache["state"] + 1.5
    bt = np.zeros((ROWS, NBLK), np.int32)
    bt[1, :4] = [4, 5, 6, 7]
    pos = np.asarray([0, 9, 0], np.int32)
    _, cache = _tick(params, cfg, cache, bt, pos, np.asarray([0, 3, 0]))
    state = np.asarray(cache["state"])
    assert (state[:, 0] == 1.5).all() and (state[:, 2] == 1.5).all()
    assert not (state[:, 1] == 1.5).all()


# ---------------------------------------------- the benchmark's files

def test_the_benchmarks_init_is_the_programs(arch, model):
    cfg, _ = model
    key = jax.random.PRNGKey(11)
    for dtype in (jnp.float32, jnp.bfloat16):
        ours = jax.jit(lambda k: arch.init(cfg, k, dtype))(key)
        theirs = jax.jit(lambda k: ms.init_params(cfg, k, dtype))(key)
        assert jax.tree_util.tree_structure(ours) \
            == jax.tree_util.tree_structure(theirs)
        # seeded attention is made peaked (a logit std of
        # SEEDED_ATTN_LOGIT_STD) through the attention layers' q and k
        # norms; every other leaf is the program's, bit for bit
        gain = np.float32(arch.SEEDED_ATTN_LOGIT_STD ** 0.5)
        for (path, a), b in zip(
                jax.tree_util.tree_leaves_with_path(ours),
                jax.tree_util.tree_leaves(theirs)):
            assert a.dtype == b.dtype and a.shape == b.shape
            peaked = path[0].key == "runs" \
                and path[-1].key in ("qn", "kn") \
                and cfg.runs[path[1].idx][0] == ms.ATTN
            np.testing.assert_array_equal(
                np.asarray(a, np.float32),
                np.asarray(b, np.float32) * (gain if peaked else 1))
        assert gain > 1


def test_the_benchmarks_seeded_attention_shows_the_selection(
        arch, model, reference):
    """What `init` scales the q and k norms for: with the program's
    plain seeded weights attention is near uniform and switching the
    selection off hardly moves a logit; with the benchmark's it does,
    so the cell's check can fail a wrong or missing block."""
    cfg, _ = model
    toks = jnp.asarray(_tokens(96, seed=5))
    dense = dict(C, sparse_config=dict(C["sparse_config"], dense_len=10**6))

    def moved(params):
        want = reference.forward(params, toks, C, query_block=16)
        off = reference.forward(params, toks, dense, query_block=16)
        return float(jnp.abs(want - off)[40:].max())
    key = jax.random.PRNGKey(3)
    flat = moved(ms.init_params(cfg, key, jnp.float32))
    peaked = moved(arch.init(cfg, key, jnp.float32))
    assert peaked > 1.5 * flat > 0, (peaked, flat)   # 2.2 x at toy size


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
        lambda name, *a: None if name == "ray_tpu.models.minicpm_sala"
        else real(name, *a))
    spec = importlib.util.spec_from_file_location(
        "sala_arch_probe", os.path.join(ARCH_DIR, "__init__.py"),
        submodule_search_locations=[ARCH_DIR])
    mod = importlib.util.module_from_spec(spec)
    with pytest.raises(ImportError, match="ray_tpu.models.minicpm_sala"):
        spec.loader.exec_module(mod)


def _real_config():
    with open(os.path.join(BENCH, "configs", "minicpm-sala-d16.json")) as f:
        return json.load(f)


def test_costs_against_hand_counts(arch):
    c = _real_config()
    attn = 3 * 4096 * 4096 + 2 * 4096 * 256 + 3 * 4096 * 16384
    lin = 5 * 4096 * 4096 + 3 * 4096 * 16384
    assert (attn, lin) == (253_755_392, 285_212_672)
    assert arch.layer_matmul_params(c, "minicpm4") == attn
    assert arch.layer_matmul_params(c, "lightning-attn") == lin
    layers = 4 * attn + 12 * lin
    assert arch.matmul_params(c) == layers + 4096 * 73448
    assert 5.03e9 < arch.total_params(c) < 5.05e9       # 10.08 GB of bf16
    assert arch.kv_bytes_per_token(c) == 4096           # 4 layers x K+V x 2 x 128
    state = 12 * 32 * 128 * 128 * 4                     # a row's, float32
    # a tick of 8 rows at 16k of context each: weights once, the state
    # read and written, 4,096 keys a row and layer, 1,024 compressed keys
    tick = arch.decode_tick(c, 8, 8 * 16384)
    want = arch.matmul_params(c) * 2 + 8 * 4096 * 2 + 8 * 4096 \
        + 2 * 8 * state + 8 * 4096 * 4096 + 4 * 2 * 128 * 4 * 8 * 1024
    assert tick["bytes"] == want
    # ...and below dense_len: all a row holds, nothing scored
    tick = arch.decode_tick(c, 8, 8 * 1000)
    assert tick["bytes"] == arch.matmul_params(c) * 2 + 8 * 4096 * 2 \
        + 8 * 4096 + 2 * 8 * state + 8 * 1001 * 4096
    # a sparse chunk is bound by its matmuls
    chunk = arch.prefill_chunk(c, 512, 16384, with_head=False)
    assert 2 * layers * 512 < chunk["flops"] < 1.1 * 2 * layers * 512
    assert chunk["bytes"] > layers * 2 + 2 * state
    for kernel, args in (("lightning_step", (8,)), ("lightning_chunk", (512,)),
                         ("sparse_score", (8 * 16384, 8 * 16384)),
                         ("sparse_attend", (8 * 4096, 8 * 4096))):
        cost = getattr(arch, kernel)(c, *args)
        assert cost["flops"] > 0 and cost["bytes"] > 0, kernel
    assert arch.lightning_step(c, 8)["bytes"] == 2 * 8 * state
    with pytest.raises(NotImplementedError, match="serves only"):
        arch.train_flops_per_token(c, 4096)


def test_the_configuration_file_holds_the_catalogs_numbers():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        rows = [json.loads(ln) for ln in f if ln.strip()]
    row = next(r for r in rows if r["name"] == "MiniCPM-SALA")
    c = _real_config()
    assert c["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if c.get(k) != v)
    assert differs == c["reduced"] == ["num_hidden_layers"]
    assert c["layer_mixers"] == row["config"]["mixer_types"][::2]
    assert [i for i, m in enumerate(c["layer_mixers"]) if m == A] \
        == [0, 8, 11, 15]
    assert c["published"]["num_hidden_layers"] \
        == row["config"]["num_hidden_layers"]


# ------------------------------------------------------------- guards

@pytest.fixture(scope="module")
def served(model):
    cfg, params = model
    eng = GenerationEngine(params, cfg, num_slots=ROWS, page_size=PSZ,
                           prefill_chunk=CHUNK, kv_pages=64,
                           enable_prefix_cache=False)
    yield eng
    eng.stop()


@pytest.mark.parametrize("what", [
    "prefix_cache", "kv_tiering", "kv_export", "kv_import", "session",
    "session_resurrect", "migrate_local", "speculation", "page_size",
    "prefill_chunk"])
def test_what_cannot_carry_per_row_state_refuses_by_name(model, served, what):
    cfg, params = model
    kw = dict(num_slots=ROWS, page_size=PSZ, prefill_chunk=CHUNK,
              enable_prefix_cache=False)
    missing = "state snapshots at page boundaries"
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
    elif what == "page_size":
        with pytest.raises(ValueError, match="selection block"):
            GenerationEngine(params, cfg, **dict(kw, page_size=16))
    else:
        with pytest.raises(ValueError, match="whole blocks"):
            GenerationEngine(params, cfg, **dict(kw, prefill_chunk=12))


def test_the_engine_serves_it_and_counts(model, served, reference):
    """Five requests on three rows: greedy tokens equal the reference's
    argmax chain, and the new counters add up."""
    cfg, params = model
    before = served.stats().to_dict()
    prompts = [_tokens(n, seed=n).tolist() for n in (77, 20, 45, 60, 33)]
    outs = [s.result(timeout=300) for s in
            [served.submit(p, max_new_tokens=6) for p in prompts]]
    for p, out in zip(prompts, outs):
        seq = list(p)
        for _ in range(6):
            logits = reference.forward(params, jnp.asarray(seq, jnp.int32),
                                       C, query_block=16)
            seq.append(int(np.asarray(logits[-1]).argmax()))
        assert out == seq[len(p):]
    gain = {k: v - before[k] for k, v in served.stats().to_dict().items()
            if isinstance(v, (int, float))}
    assert gain["state_resets"] == 5
    assert gain["prefill_tokens"] == sum(map(len, prompts))
    # chunks that start at or past the toy dense_len of 32
    assert gain["prefill_tokens_sparse"] == sum(max(0, len(p) - 32)
                                                for p in prompts)
    assert 0 < gain["attn_keys_attended"] < gain["attn_keys_resident"]
    assert gain["attn_keys_resident"] % cfg.n_attn == 0
    # a model with its own step reports what it reads as gathered
    assert gain["attn_keys_gathered"] == gain["attn_keys_attended"]
    assert served._lander is None       # no tiers: no lander thread


def test_llmserver_serves_with_the_prefix_cache_off(model):
    import asyncio

    from ray_tpu.serve.llm.api import LLMServer
    cfg, params = model
    with pytest.raises(NotImplementedError, match="prefix cache"):
        LLMServer(lambda: (params, cfg),
                  {"num_slots": 2, "page_size": PSZ, "prefill_chunk": CHUNK})
    server = LLMServer(lambda: (params, cfg), {
        "num_slots": 2, "page_size": PSZ, "prefill_chunk": CHUNK,
        "enable_prefix_cache": False})
    try:
        out = asyncio.run(server.generate(_tokens(40).tolist(),
                                          max_new_tokens=5))
        assert len(out) == 5
        assert server.engine.stats().state_resets == 1
    finally:
        server.engine.stop()


# ------------------------------------- the toy configuration as a cell

def test_the_toy_configuration_is_served_to_correct(tmp_path):
    """As benchmarks/tests/rehearsal does for a second architecture: a
    temporary benchmark root gets a configuration that names
    `minicpm_sala`, a long-document mix at toy size and a cell; the
    benchmark's own run serves it and its check (48 + 4 positions, the
    last chunk and every tick selecting) comes out correct."""
    sys.path.insert(0, os.path.join(BENCH, "tests"))
    try:
        import toy
    finally:
        sys.path.pop(0)
    from benchmarks import run as bench_run
    from benchmarks.lib.registry import Registry

    root = toy.build(str(tmp_path))
    b = os.path.join(root, "bm")
    with open(os.path.join(b, "configs", "toy-sala.json"), "w") as f:
        json.dump(C, f)
    with open(os.path.join(b, "traffic", "longdoc-toy.json"), "w") as f:
        json.dump({"kind": "serve", "loop": "closed", "clients": 4,
                   "block": 4, "blocks": 64, "warmup_first_tokens": 2,
                   "prompt_len": {"dist": "lognormal", "median": 56,
                                  "sigma": 0.25, "min": 34, "max": 96},
                   "output_len": {"dist": "fixed", "value": 6},
                   "trace_seconds": 1}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "toy-sala", "source": "none",
                            "file": "bm/configs/toy-sala.json",
                            "reduced": [], "why": "toy"})
    spec["workloads"].append({"name": "sala-toy", "config": "toy-sala",
                              "traffic": "longdoc-toy", "chips": 1,
                              "why": "toy"})
    for m in spec["end_to_end"]:
        if m["name"] == "out_tok_per_s":
            m["workloads"].append("sala-toy")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    lines = []
    out = bench_run.run_cell(Registry(root), "sala-toy", seed=2**31 + 29,
                             seconds=4.0, trace=False, platform="cpu",
                             init_kwargs={"num_cpus": 6}, emit=lines.append)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"out_tok_per_s", "setup_s"}
    check = json.loads(lines[0])["check"]
    assert check["finite"] and check["positions"] == 52
    assert check["max_abs_diff"] <= 1e-3 and check["argmax_equal"] == 52
