"""The harness rehearsed at toy size on the CPU: the last line's
contract, a generator whose totals do not depend on the seed, the
sub-window arithmetic, a measurement run that refuses the CPU, cells,
metrics and architectures added as files, and BENCHMARK.json held to
its own files."""

import ast
import glob
import json
import os
import re
import subprocess
import sys

import pytest

import toy
from benchmarks import run as bench_run
from benchmarks.lib import checks, stats, traffic
from benchmarks.lib.costs import BF16, min_time
from benchmarks.lib.peaks import peaks_for
from benchmarks.lib.registry import Registry, arch_of

REPO = toy.REPO
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


# What `replica.probe_check_logits` returned on the parent commit (5701278,
# before its body moved to lib/checks.py) for the toy cells at these seeds:
# recorded on the CPU of this sandbox (jax 0.9.0, float32), twice, equal.
# The move changed no program, order, prompt or arithmetic: the ten keys
# are equal to the last bit.  (The differences are a few float32 ulps of
# logits ~0.5: another CPU or XLA may round them otherwise; the equality
# held is between two commits on one machine.)
RECORDED = {
    "mistral7b-chat": (2**31 + 11, (
        24, 20, 4, True,
        1.1920928955078125e-07, 1.1920928955078125e-07, 8.940696716308594e-08,
        1.683522832252038e-08, 0.16065098345279694, 24)),
    "internlm2-batch": (2**31 + 12, (
        24, 20, 4, True,
        1.2665987014770508e-07, 1.2665987014770508e-07, 8.940696716308594e-08,
        1.7306327038113523e-08, 0.16282063722610474, 24)),
    "mistral7b-doc": (2**31 + 13, (
        24, 20, 4, True,
        1.1920928955078125e-07, 1.1920928955078125e-07, 1.1920928955078125e-07,
        1.687980422104829e-08, 0.15989892184734344, 24)),
    "gpt-chat": (2**31 + 17, (
        24, 20, 4, True,
        1.4901161193847656e-07, 1.4901161193847656e-07, 1.1920928955078125e-07,
        2.153040945529483e-08, 0.15856285393238068, 24)),
}


def held_to_the_parents(check: dict, cell: str) -> None:
    assert tuple(check[k] for k in checks.COMPARED) == RECORDED[cell][1]
    assert check["procedure"] == "benchmarks.lib.checks.default"
    assert check["programs"] == ["jit__prefill_chunk", "jit__paged_tick"]


@pytest.fixture(scope="module")
def toy_reg(tmp_path_factory):
    return Registry(toy.build(str(tmp_path_factory.mktemp("toyroot"))))


# ------------------------------------------------------ the last line

@pytest.mark.parametrize("cell", ["mistral7b-chat", "internlm2-batch",
                                  "mistral7b-doc", "internlm2-train4"])
def test_run_prints_the_contracts_object(toy_reg, cell):
    """One whole run of each cell (serve.llm or JaxTrainer, reference
    check included) on the CPU at toy size."""
    lines = []
    seed = RECORDED[cell][0] if cell in RECORDED else 2**31 + 11
    out = bench_run.run_cell(toy_reg, cell, seed=seed, seconds=4.0,
                             trace=False, platform="cpu",
                             init_kwargs={"num_cpus": 6}, emit=lines.append)
    assert set(out) == {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    want = {m["name"]: m["unit"]
            for m in toy_reg.metrics_for(cell, "end_to_end")}
    assert {k: v["unit"] for k, v in out["metrics"].items()} == want
    assert "setup_s" in want and len(want) >= 2
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert out["device"]["platform"] == "cpu"       # and never printed
    json.loads(json.dumps(out))                     # as a chip result
    diag = json.loads(lines[0])                     # the earlier line
    if cell != "internlm2-train4":
        assert diag["requests"]["failed"] == 0
        assert len(diag["gaps_by_subwindow"]["p90"]) == 5
        assert diag["gaps_whole_window"]["n"] == sum(
            r["n"] for r in diag["gaps_by_subwindow"]["p90"])
        assert diag["check"]["max_abs_diff"] <= 1e-3
        held_to_the_parents(diag["check"], cell)
        assert diag["check_programs_not_in_trace"] is None    # untraced
        assert diag["compile_cache_entries"][0] == \
            diag["compile_cache_entries"][1]


def test_measurement_run_on_the_cpu_fails():
    """The command itself has no option under which a CPU passes."""
    r = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "mistral7b-chat",
         "--seed", "1", "--seconds", "2", "--trace", "0"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "TPU chip(s) on this host" in r.stderr


# -------------------------------------------------- traffic and arithmetic

def test_stratified_generator_offers_the_same_work_whatever_the_seed():
    mix = Registry(REPO).traffic("chat")
    a = traffic.schedule(mix, 1, 45.0, 4)
    b = traffic.schedule(mix, 2**31 + 7, 45.0, 4)
    assert traffic.totals(a) == traffic.totals(b)
    n = traffic.block_size(mix, 45.0)
    for blk in range(4):            # every block: the same multisets
        sa, sb = a[blk * n:(blk + 1) * n], b[blk * n:(blk + 1) * n]
        for key in ("prompt_len", "max_new"):
            assert sorted(r[key] for r in sa) == sorted(r[key] for r in sb)
        lo, hi = blk * 15.0, (blk + 1) * 15.0
        assert all(lo <= r["due"] < hi for r in sa + sb)
    assert [r["prompt_len"] for r in a] != [r["prompt_len"] for r in b]
    lens = [r["prompt_len"] for r in a[:n]]
    assert min(lens) >= mix["prompt_len"]["min"]
    assert max(lens) <= mix["prompt_len"]["max"]
    assert traffic.prompt_tokens(5, 0, 8, 100) != \
        traffic.prompt_tokens(5, 1, 8, 100)
    assert traffic.prompt_tokens(2**31 + 5, 3, 8, 100) == \
        traffic.prompt_tokens(2**31 + 5, 3, 8, 100)


def test_subwindow_median_arithmetic():
    # five sub-windows of 2 s; p95 of each is its largest value here
    events = [(0.5, 10), (1.5, 11), (2.5, 50), (3.0, 12), (4.1, 13),
              (6.2, 14), (8.9, 15), (10.0, 99), (-1.0, 99)]
    value, readings = stats.subwindow_pct(events, 0.0, 10.0, 0.95, 5)
    assert [r["n"] for r in readings] == [2, 2, 1, 1, 1]
    assert [r["value"] for r in readings] == [11, 50, 13, 14, 15]
    assert value == 14                      # one slow sub-window is outvoted
    assert stats.subwindow_pct([], 0, 1, 0.95, 5)[0] is None
    assert stats.pct([1, 2, 3, 4, 5], 0.5) == 3
    assert stats.pct([], 0.5) is None
    assert abs(stats.iqr_share([100, 101, 102, 103, 104, 105]) - 3.5 / 102.5) \
        < 1e-3


def test_the_judged_gap_percentile_is_over_all_gaps_of_the_window():
    """`itl_p90_ms` must move when the window holds stalls; the median
    of sub-window readings (kept as a per-layer metric) does not."""
    reg = Registry(REPO)
    read = reg.reader(reg.metric("itl_p90_ms")["reader"])
    assert "parts" not in reg.metric("itl_p90_ms")["args"]
    assert reg.metric("itl_p90_sub_ms.chat")["args"]["parts"] == 5

    class Req:
        def __init__(self, gaps):
            self.token_times = [0.0]
            for g in gaps:
                self.token_times.append(self.token_times[-1] + g)

    steady = [0.1] * 40                      # 4 s of 100 ms gaps
    stalled = [0.1] * 20 + [0.3] * 5 + [0.1] * 5   # stalls 1/6 of gaps, late
    for gaps, whole in ((steady, 100.0), (stalled, 300.0)):
        obs = {"t_w": 0.0, "t_end": 5.0, "requests": [Req(gaps)]}
        assert abs(read(obs, **reg.metric("itl_p90_ms")["args"])
                   - whole) < 1e-6
        assert abs(read(obs, **reg.metric("itl_p90_sub_ms.chat")["args"])
                   - 100.0) < 1e-6


def test_training_is_held_to_the_pinned_loss_curve():
    job = Registry(REPO).traffic("train4k")
    curve = job["loss_curve"]
    assert curve["step"][0] == 0 and len(curve["step"]) == \
        len(curve["value"]) == len(curve["tolerance"])

    def correct(losses):
        obs = {"traffic": job, "train": {
            "warm": [[0.0, 1.0, x] for x in losses[:3]],
            "steps": [[0.0, 1.0, x] for x in losses[3:]],
            "t_init": 0.0, "mesh": {}, "params": 0, "cache0": 0,
            "cache1": 0}}
        return bench_run._train_summary(obs)["correct"]

    on = dict(zip(curve["step"], curve["value"]))
    last = max(on)
    losses, k0 = [], 0
    for k in range(last + 1):                # straight lines between pins
        k0 = max(s for s in on if s <= k)
        k1 = min(s for s in on if s >= k)
        w = 0 if k1 == k0 else (k - k0) / (k1 - k0)
        losses.append(on[k0] * (1 - w) + on[k1] * w)
    assert correct(losses)
    assert correct(losses[:10])              # held to the steps it reached
    assert not correct([losses[0]] * len(losses))         # no update
    assert not correct([losses[0] - (losses[0] - x) * 2 for x in losses])
    assert not correct(losses[:5] + [float("nan")] + losses[6:])


# ---------------------------------------------- cells and metrics are data

def test_a_cell_a_config_a_mix_a_metric_a_reader_and_an_architecture_are_only_new_files(  # noqa: E501
        tmp_path):
    """A later PR adds files and one entry each; nothing is edited."""
    root = toy.build(str(tmp_path))
    b = os.path.join(root, "bm")
    os.makedirs(os.path.join(b, "readers"))
    os.makedirs(os.path.join(b, "archs"))
    with open(os.path.join(b, "configs", "dummy.json"), "w") as f:
        json.dump(dict(toy.CONFIG, name="dummy", hidden_size=32,
                       arch="halved"), f)
    with open(os.path.join(b, "archs", "halved.py"), "w") as f:
        f.write("def kv_bytes_per_token(c):\n"
                "    return c['hidden_size'] // 2\n")
    with open(os.path.join(b, "traffic", "burst.json"), "w") as f:
        json.dump(dict(toy.MIXES["chat"], rate_rps=9.0), f)
    with open(os.path.join(b, "metrics", "answer.burst.json"), "w") as f:
        json.dump({"reader": "answer", "args": {"plus": 1}}, f)
    with open(os.path.join(b, "readers", "answer.py"), "w") as f:
        f.write("def read(obs, plus):\n    return obs['x'] + plus\n")
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    spec["configs"].append({"name": "dummy", "source": "none",
                            "file": "bm/configs/dummy.json", "reduced": [],
                            "why": "dummy"})
    spec["workloads"].append({"name": "dummy-burst", "config": "dummy",
                              "traffic": "burst", "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "answer.burst", "unit": "n",
                              "better": "higher", "source": "host_clock",
                              "layer": "x", "moves": "itl_p90_ms",
                              "workloads": ["dummy-burst"]})
    # a quantity already defined, in the new cell: an entry and no file
    spec["per_layer"].append({"name": "device_idle_share.burst", "unit": "%",
                              "better": "lower", "source": "device_trace",
                              "layer": "Device", "moves": "itl_p90_ms",
                              "workloads": ["dummy-burst"]})
    for m in spec["end_to_end"]:
        if m["name"] == "itl_p90_ms":
            m["workloads"].append("dummy-burst")
    json.dump(spec, open(os.path.join(root, "BENCHMARK.json"), "w"))

    reg = Registry(root)
    cell = reg.cell("dummy-burst")
    assert reg.config(cell["config"])["hidden_size"] == 32
    # the configuration's architecture: a flat module of the new root,
    # found by the name in the file, in the driver and (by the
    # registry's directory alone) in a worker
    halved = arch_of(reg.config("dummy"), reg.dir)
    assert halved.__file__ == os.path.join(b, "archs", "halved.py")
    assert arch_of(reg.config("dummy"), reg.dir) is halved   # loaded once
    assert halved.kv_bytes_per_token(reg.config("dummy")) == 16
    assert reg.reader("hbm_filled")(
        {"arch": halved, "config": dict(toy.CONFIG, hidden_size=2e9),
         "replica_info": {"bytes_in_use": [5e9]},
         "samples": [{"kv_blocks_free": 32, "kv_blocks_total": 64}]}) == \
        5.0 - 0.5 * 64 * 8                 # the new module's bytes a token
    # one with no `arch` key is the default's, from the benchmark itself
    assert arch_of(reg.config("toy"), reg.dir).__file__ == os.path.join(
        toy.BENCH, "archs", "llama", "__init__.py")
    assert reg.traffic(cell["traffic"])["rate_rps"] == 9.0
    names = [m["name"] for m in reg.metrics_for("dummy-burst", "per_layer")]
    # others list their own cells
    assert names == ["answer.burst", "device_idle_share.burst"]
    assert not os.path.exists(os.path.join(
        b, "metrics", "device_idle_share.burst.json"))
    assert reg.metric("device_idle_share.burst") == \
        reg.metric("device_idle_share.chat") == \
        {"reader": "device_idle", "args": {}}
    assert reg.read_metrics("dummy-burst", "per_layer", {"x": 41}) == \
        {"answer.burst": {"value": 42.0, "unit": "n"}}   # no trace: left out
    e2e = [m["name"] for m in reg.metrics_for("dummy-burst", "end_to_end")]
    assert e2e == ["itl_p90_ms", "setup_s"]
    # the benchmark's own readers stay reachable from the new root
    assert reg.reader("setup_seconds")({"t_w": 3.0, "t_proc0": 1.0}) == 2.0


def test_benchmark_json_agrees_with_its_files():
    reg = Registry(REPO)
    spec = reg.spec
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["paths"] == ["benchmarks"] and 1 <= spec["run_seconds"] <= 51
    cells = {w["name"]: w for w in spec["workloads"]}
    assert sum(w["chips"] == 4 for w in cells.values()) \
        <= max(1, len(cells) // 4)
    assert len({(w["config"], w["traffic"]) for w in cells.values()}) \
        == len(cells)
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in e2e.values():
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for c in spec["configs"]:
        assert c["file"].startswith("benchmarks/") and NAME.match(c["name"])
        body = reg.config(c["name"])
        assert body["source"] == c["source"] and \
            body["reduced"] == c["reduced"]
    for w in cells.values():
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        reg.config(w["config"]), reg.traffic(w["traffic"])
        mine = reg.metrics_for(w["name"], "end_to_end")
        assert len(mine) >= 2 and reg.metrics_for(w["name"], "per_layer")
    for group in ("end_to_end", "per_layer"):
        for m in spec[group]:
            assert NAME.match(m["name"])
            assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
            body = reg.metric(m["name"])        # BENCHMARK.json owns the rest
            assert set(body) == {"reader", "args"}, m["name"]
            reg.reader(body["reader"])
            for cell in m.get("workloads", []):
                assert cell in cells
            if group == "per_layer":
                assert set(m) <= {"name", "unit", "better", "source",
                                  "layer", "moves", "workloads"}
                # its end-to-end metric is reported wherever it is
                on = set(m.get("workloads", cells))
                target = e2e[m["moves"]]
                assert on <= set(target.get("workloads", cells))


# ------------------------------------------------------------ the yardstick

def test_costs_match_the_published_sizes():
    reg = Registry(REPO)
    mistral = reg.config("mistral-7b-v0.3-d16")
    intern = reg.config("internlm2-1.8b")
    costs = arch_of(mistral, reg.dir)
    assert costs is arch_of(intern, reg.dir)
    assert costs.layer_matmul_params(mistral) == 218_103_808
    assert costs.total_params(mistral) == 16 * 218_103_808 \
        + 2 * 134_217_728 + 33 * 4096
    assert costs.kv_bytes_per_token(mistral) == 64 * 1024
    assert costs.layer_matmul_params(intern) == 62_914_560
    assert round(costs.total_params(intern) / 1e9, 2) == 1.89
    assert costs.kv_bytes_per_token(intern) == 96 * 1024
    peaks = peaks_for("TPU v5 lite")
    tick = min_time(costs.decode_tick(mistral, 10, 3000), peaks)
    assert tick["bound"] == "memory" and 0.009 < tick["seconds"] < 0.0095
    chunk = min_time(costs.prefill_chunk(mistral, 32, 512, True), peaks)
    assert chunk["bound"] == "memory"
    # 6 x 1.70e9 matmul parameters + causal attention over 4096
    per_tok = costs.train_flops_per_token(intern, 4096)
    assert 1.13e10 < per_tok < 1.15e10
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")


# --------------------------------------------------------- architectures

def _frozen_init(cfg, key, dtype):
    """`lib/model._init` as PR 26 had it, verbatim: the weights every
    recorded reading was taken on."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    L, D, H, Hk, Dh, F = (cfg.n_layers, cfg.d_model, cfg.n_heads,
                          cfg.n_kv_heads, cfg.head_dim, cfg.d_ff)
    s = 0.02
    so = s / np.sqrt(2 * L)
    k = iter(jax.random.split(key, 8))

    def nrm(shape, scale):
        return (scale * jax.random.normal(next(k), shape, jnp.float32)
                ).astype(dtype)

    ones = lambda shape: jnp.ones(shape, jnp.float32)  # noqa: E731
    return {
        "wte": nrm((cfg.vocab_size, D), s),
        "blocks": {
            "ln1": ones((L, D)), "wq": nrm((L, D, H, Dh), s),
            "wkv": nrm((L, D, 2, Hk, Dh), s), "wo": nrm((L, H, Dh, D), so),
            "ln2": ones((L, D)), "w_gate": nrm((L, D, F), s),
            "w_up": nrm((L, D, F), s), "w_down": nrm((L, F, D), so)},
        "ln_f": ones((D,)),
        "wlm": nrm((D, cfg.vocab_size), s),
    }


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_default_architecture_draws_the_weights_it_always_drew(dtype):
    """Same seed, same weights, bit for bit: the move changed no key,
    order, shape, scale or cast."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.lib.model import seed_key

    arch = arch_of(toy.CONFIG)
    cfg = arch.build(toy.CONFIG, max_seq=128, remat=False)
    dt = getattr(jnp, dtype)
    key = seed_key(2**31 + 5)
    new = jax.jit(lambda k: arch.init(cfg, k, dt))(key)
    old = jax.jit(lambda k: _frozen_init(cfg, k, dt))(key)
    assert jax.tree_util.tree_structure(new) == \
        jax.tree_util.tree_structure(old)
    for a, b in zip(jax.tree_util.tree_leaves(new),
                    jax.tree_util.tree_leaves(old)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(np.asarray(a.astype(jnp.float32)),
                              np.asarray(b.astype(jnp.float32)))
    assert new["wlm"].dtype == dt and float(jnp.abs(new["wlm"]).max()) > 0


def test_an_architecture_without_a_file_fails_by_name(tmp_path):
    root = toy.build(str(tmp_path))
    with open(os.path.join(root, "bm", "configs", "toy.json"), "w") as f:
        json.dump(dict(toy.CONFIG, arch="mamba9"), f)
    reg = Registry(root)
    with pytest.raises(KeyError, match="archs/mamba9"):
        arch_of(reg.config("toy"), reg.dir)
    with pytest.raises(KeyError, match="archs/mamba9"):   # before any start
        bench_run.run_cell(reg, "mistral7b-chat", seed=1, seconds=1.0,
                           trace=False, platform="cpu")


REFERENCES = sorted(
    glob.glob(os.path.join(toy.BENCH, "archs", "*", "reference.py"))
    + glob.glob(os.path.join(toy.HERE, "rehearsal", "archs", "*",
                             "reference.py")))


@pytest.mark.parametrize("path", REFERENCES, ids=[
    os.path.relpath(p, toy.BENCH) for p in REFERENCES])
def test_a_plain_reference_imports_nothing_of_the_program(path):
    """Neither `ray_tpu` nor the harness: jax and the standard library."""
    with open(path) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add("." if node.level else node.module.split(".")[0])
    assert imported <= {"__future__", "jax", "math"}, imported


def test_every_architecture_of_the_benchmark_gives_what_the_harness_asks():
    reg = Registry(REPO)
    assert len(REFERENCES) >= 2
    for c in reg.spec["configs"]:
        arch = arch_of(reg.config(c["name"]), reg.dir)
        for name in ("build", "init", "reference", "matmul_params",
                     "total_params", "kv_bytes_per_token", "decode_tick",
                     "prefill_chunk", "train_flops_per_token"):
            assert callable(getattr(arch, name)), (c["name"], name)


# ------------------------------------- a second architecture, as new files

@pytest.fixture(scope="module")
def gpt_reg(tmp_path_factory):
    before = sorted(os.listdir(toy.BENCH)), open(
        os.path.join(REPO, "BENCHMARK.json")).read()
    root = toy.add_gpt(toy.build(str(tmp_path_factory.mktemp("gptroot"))))
    assert before == (sorted(os.listdir(toy.BENCH)), open(
        os.path.join(REPO, "BENCHMARK.json")).read())
    assert not os.path.exists(os.path.join(toy.BENCH, "archs", "gpt"))
    return Registry(root)


def test_a_second_architecture_serves_a_cell_against_its_own_reference(
        gpt_reg):
    """The GPT block (learned positions, fused wqkv, GELU, two
    feed-forward matrices): the other arm of the program's decode
    path, brought by `archs/gpt/` in a temporary root."""
    lines = []
    out = bench_run.run_cell(gpt_reg, "gpt-chat",
                             seed=RECORDED["gpt-chat"][0],
                             seconds=4.0, trace=False, platform="cpu",
                             init_kwargs={"num_cpus": 6}, emit=lines.append)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"itl_p90_ms", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    check = json.loads(lines[0])["check"]
    assert check["finite"] and check["positions"] == 24
    assert check["max_abs_diff"] <= 1e-3 and check["mean_abs_diff"] <= 1e-4
    assert check["argmax_equal"] == 24
    # and the default architecture's reference does not fit these weights
    assert check["reference_logit_std"] > 100 * check["max_abs_diff"]
    held_to_the_parents(check, "gpt-chat")


def test_the_yardstick_readers_count_with_the_cells_architecture(gpt_reg):
    """`paged_tick_roofline`, `prefill_chunk_roofline` and `hbm_filled_gb`
    in a cell of the GPT architecture: two feed-forward matrices a layer,
    not three; as many KV heads as heads."""
    from benchmarks.lib.serve_cell import Request

    c = gpt_reg.config("toy-gpt")
    gpt, default = arch_of(c, gpt_reg.dir), arch_of(toy.CONFIG)
    D, F, L, V = 64, 128, 2, 256
    assert gpt.layer_matmul_params(c) == 4 * D * D + 2 * D * F
    assert default.layer_matmul_params(c) == 4 * D * D + 3 * D * F
    assert gpt.matmul_params(c) == L * (4 * D * D + 2 * D * F) + D * V
    assert gpt.kv_bytes_per_token(c) == L * 2 * D * BF16
    assert gpt.total_params(c) == gpt.matmul_params(c) + (V + 128) * D \
        + (2 * L + 1) * D

    req = Request({"index": 0, "due": 0.0, "prompt_len": 40, "max_new": 5})
    req.sent, req.token_times = 10.0, [10.5, 11.0, 11.5, 12.0, 12.5]
    obs = {"arch": gpt, "config": c, "requests": [req], "t_w": 9.0,
           "t_end": 20.0, "trace_t0": 10.0, "trace_t1": 13.0,
           "trace": {"programs": {"jit__paged_tick": [2e-6] * 4,
                                  "jit__prefill_chunk": [3e-6, 5e-6]}},
           "replica_info": {"kind": "TPU v5 lite", "bytes_in_use": [3e9]},
           "samples": [{"kv_blocks_free": 16, "kv_blocks_total": 64}]}
    got = gpt_reg.read_metrics("gpt-chat", "per_layer", obs)
    peaks = peaks_for("TPU v5 lite")
    # four decode ticks of one row, contexts 41..44
    tick = min_time(gpt.decode_tick(c, 1.0, (41 + 42 + 43 + 44) / 4), peaks)
    assert got["paged_tick_roofline.gpt"]["value"] == pytest.approx(
        100 * tick["seconds"] * 4 / 8e-6)
    chunks = [min_time(gpt.prefill_chunk(c, 32, 0, False), peaks),
              min_time(gpt.prefill_chunk(c, 8, 32, True), peaks)]
    assert got["prefill_chunk_roofline.gpt"]["value"] == pytest.approx(
        100 * (chunks[0]["seconds"] + chunks[1]["seconds"]) / 2 / 4e-6)
    assert got["hbm_filled_gb.gpt"]["value"] == pytest.approx(
        (3e9 - 0.25 * 64 * 8 * L * 2 * D * BF16) / 1e9)
    # the same observations on the default's yardstick read otherwise
    other = gpt_reg.read_metrics("gpt-chat", "per_layer",
                                 dict(obs, arch=default))
    assert other["paged_tick_roofline.gpt"]["value"] > \
        got["paged_tick_roofline.gpt"]["value"]


def test_a_training_cell_on_a_serve_only_architecture_says_so(gpt_reg):
    with pytest.raises(RuntimeError, match="only serves: it has no "
                       "param_specs, make_train_step, batch_axes"):
        bench_run.run_cell(gpt_reg, "gpt-train", seed=1, seconds=1.0,
                           trace=False, platform="cpu")


# ------------------- an architecture that brings its own logits check

# Two more, written into the temporary root as flat modules: `gpt_verify`
# whole, but for the one name each replaces.
VARIANT = '''
import os

from benchmarks.lib.registry import find_module

_base = find_module("archs", "gpt_verify", (os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))),))
globals().update({k: v for k, v in vars(_base).items()
                  if not k.startswith("_")})

'''
OTHER_WEIGHTS = VARIANT + '''
def reference(params, tokens, c):
    """The plain reference, given a head that is not the program's."""
    return _base.reference(dict(params, wlm=params["wlm"][:, ::-1]),
                           tokens, c)
'''
DROPS_A_KEY = VARIANT + '''
def check_logits(*args):
    result = _base.check_logits(*args)
    del result["mean_abs_diff"]
    return result
'''


@pytest.fixture(scope="module")
def checked_reg(tmp_path_factory):
    before = sorted(os.listdir(toy.BENCH)), open(
        os.path.join(REPO, "BENCHMARK.json")).read()
    root = toy.add_gpt(toy.build(str(tmp_path_factory.mktemp("checked"))))
    toy.add_checked(root)
    toy.add_checked(root, "other_weights", OTHER_WEIGHTS)
    toy.add_checked(root, "drops_a_key", DROPS_A_KEY)
    assert before == (sorted(os.listdir(toy.BENCH)), open(
        os.path.join(REPO, "BENCHMARK.json")).read())
    assert not glob.glob(os.path.join(toy.BENCH, "archs", "gpt*"))
    return Registry(root)


def _checked_run(reg, cell):
    lines = []
    out = bench_run.run_cell(reg, cell, seed=2**31 + 58, seconds=3.0,
                             trace=False, platform="cpu",
                             init_kwargs={"num_cpus": 6}, emit=lines.append)
    return out, json.loads(lines[0])["check"]


def test_an_architectures_own_procedure_decides_correct(checked_reg):
    """`archs/gpt_verify.py` decodes through `_paged_verify` at two
    columns a row: the run reports that procedure and those programs,
    and `correct` is its comparison's."""
    out, check = _checked_run(checked_reg, "gpt_verify-chat")
    assert out["correct"] is True and out["failed"] == 0
    assert check["procedure"] == "archs.gpt_verify.check_logits"
    assert check["programs"] == ["jit__prefill_chunk", "jit__paged_verify"]
    assert set(check) == set(checks.COMPARED) | {"procedure", "programs",
                                                 "tolerance"}
    assert (check["positions"], check["prefill_positions"],
            check["decode_positions"], check["argmax_equal"]) \
        == (24, 20, 4, 24)
    assert check["finite"] and check["max_abs_diff"] <= 1e-3


def test_correct_is_false_when_the_reference_has_other_weights(checked_reg):
    out, check = _checked_run(checked_reg, "other_weights-chat")
    assert out["correct"] is False and out["failed"] == 0
    assert check["procedure"] == "archs.other_weights.check_logits"
    assert check["finite"] and check["positions"] == 24
    assert check["max_abs_diff"] > 100 * check["tolerance"]["max_abs_diff"]


def test_a_procedure_that_drops_a_key_fails_by_both_names(checked_reg):
    """Not `correct: false`: the run ends, naming the key and the
    architecture."""
    with pytest.raises(Exception) as e:
        _checked_run(checked_reg, "drops_a_key-chat")
    assert "mean_abs_diff" in str(e.value) and "drops_a_key" in str(e.value)
    assert "archs.drops_a_key.check_logits" in str(e.value)


def test_the_contract_is_held_by_name():
    """`checks.hold` on results as a procedure might hand them in."""
    import numpy as np

    rng = np.random.default_rng(0)
    ref = rng.normal(size=(6, 16)).astype(np.float32)
    got = ref + np.float32(0.25) * (np.arange(6)[:, None] == 5)
    good = dict(checks.compare(got, ref, 4), programs=["jit__x"])
    assert tuple(good)[:10] == checks.COMPARED
    assert (good["positions"], good["prefill_positions"],
            good["decode_positions"]) == (6, 4, 2)
    assert good["max_abs_diff_prefill"] == 0.0
    assert good["max_abs_diff_decode"] == good["max_abs_diff"] == 0.25
    assert good["mean_abs_diff"] == pytest.approx(0.25 / 6)
    assert good["argmax_equal"] >= 5 and good["finite"]

    def mine():
        pass

    held = checks.hold(good, mine, "some_arch")
    assert held["procedure"].startswith("archs.some_arch.")
    assert held["procedure"].endswith("mine")
    assert checks.hold(good, checks.default, "llama")["procedure"] \
        == "benchmarks.lib.checks.default"
    for key in checks.COMPARED + ("programs",):
        with pytest.raises(KeyError, match=f"some_arch.*{key}"):
            checks.hold({k: v for k, v in good.items() if k != key}, mine,
                        "some_arch")
    with pytest.raises(ValueError, match="some_arch"):       # no decode row
        checks.hold(dict(good, decode_positions=0, prefill_positions=6),
                    mine, "some_arch")
    with pytest.raises(ValueError, match="some_arch"):
        checks.hold(dict(good, positions=7), mine, "some_arch")
    with pytest.raises(ValueError, match="names no program"):
        checks.hold(dict(good, programs=[]), mine, "some_arch")
    with pytest.raises(TypeError, match="some_arch"):
        checks.hold(None, mine, "some_arch")
    with pytest.raises(ValueError):                  # rows that do not pair
        checks.compare(got, ref[:5], 4)


def test_the_harness_imports_without_a_backend():
    """The driver imports `benchmarks.lib` (checks and replica's seam
    with it) and starts no jax backend: the chip is the replica's."""
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys; import benchmarks.lib.checks, benchmarks.run; "
         "import jax._src.xla_bridge as xb; "
         "sys.exit(1 if xb._backends else 0)"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
