"""The harness rehearsed at toy size on the CPU: the last line's
contract, a generator whose totals do not depend on the seed, the
sub-window arithmetic, a measurement run that refuses the CPU, cells
and metrics added as data, and BENCHMARK.json held to its own files."""

import json
import os
import re
import subprocess
import sys

import pytest

import toy
from benchmarks import run as bench_run
from benchmarks.lib import costs, stats, traffic
from benchmarks.lib.peaks import peaks_for
from benchmarks.lib.registry import Registry

REPO = toy.REPO
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


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
    out = bench_run.run_cell(toy_reg, cell, seed=2**31 + 11, seconds=4.0,
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

def test_a_cell_a_config_a_mix_a_metric_and_a_reader_are_only_new_files(
        tmp_path):
    """A later PR adds files and one entry each; nothing is edited."""
    root = toy.build(str(tmp_path))
    b = os.path.join(root, "bm")
    os.makedirs(os.path.join(b, "readers"))
    with open(os.path.join(b, "configs", "dummy.json"), "w") as f:
        json.dump(dict(toy.CONFIG, name="dummy", hidden_size=32), f)
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
    assert costs.layer_matmul_params(mistral) == 218_103_808
    assert costs.total_params(mistral) == 16 * 218_103_808 \
        + 2 * 134_217_728 + 33 * 4096
    assert costs.kv_bytes_per_token(mistral) == 64 * 1024
    assert costs.layer_matmul_params(intern) == 62_914_560
    assert round(costs.total_params(intern) / 1e9, 2) == 1.89
    assert costs.kv_bytes_per_token(intern) == 96 * 1024
    peaks = peaks_for("TPU v5 lite")
    tick = costs.min_time(costs.decode_tick(mistral, 10, 3000), peaks)
    assert tick["bound"] == "memory" and 0.009 < tick["seconds"] < 0.0095
    chunk = costs.min_time(costs.prefill_chunk(mistral, 32, 512, True),
                           peaks)
    assert chunk["bound"] == "memory"
    # 6 x 1.70e9 matmul parameters + causal attention over 4096
    per_tok = costs.train_flops_per_token(intern, 4096)
    assert 1.13e10 < per_tok < 1.15e10
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
