"""A replica's start is one trace: every phase between the controller's
decision and the engine's last warm program is a span under one id, the
same timestamps are kept in the replica's books
(`LLMServer.replica_info()["start"]`), and `tracing.assemble` gives the
`start` block that `rt trace <id>` prints."""

import json
import os
import sys
import time

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu._private import jax_utils
from ray_tpu._private import tracing
from ray_tpu.serve.llm.api import llm_deployment

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")

SEVEN = ("serve.replica_start", "raylet.worker_start", "worker.boot",
         "serve.replica_init", "llm.load_model", "engine.build",
         "engine.warm")
PHASES = ("spawn", "boot", "unpickle", "load", "build")
METRICS = ("start_spawn_s", "start_boot_s", "start_load_s",
           "start_build_s", "start_warm_s", "start_unaccounted_s")
ENGINE = {"num_slots": 2, "max_seq": 64, "page_size": 8}


def _toy_loader():
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import gpt
    cfg = gpt.GPTConfig(vocab_size=97, d_model=32, n_heads=4, n_layers=2,
                        d_ff=64, max_seq=64, dtype=jnp.float32,
                        remat=False)
    return gpt.init_params(cfg, jax.random.PRNGKey(0)), cfg


def _failing_loader():
    raise RuntimeError("no weights here")


@ray_tpu.remote(num_cpus=0)
class _Holder:
    def pid(self):
        return os.getpid()


def _hold_one():
    holder = _Holder.remote()
    ray_tpu.get(holder.pid.remote(), timeout=60)
    return holder


def _hold_the_pool():
    """Take every worker the node has started ahead of demand, so the
    actors that follow each wait for a worker of their own (how =
    zygote or cold, and a boot inside the wait)."""
    from ray_tpu._private import api
    raylet = api._head_node.raylet
    holders = []
    deadline = time.time() + 60
    while time.time() < deadline:
        starting = [w for w in list(raylet.workers.values())
                    if not w.registered.is_set()]
        if raylet._idle("cpu", ""):
            holders.append(_hold_one())
        elif starting:
            time.sleep(0.05)
        else:
            return holders
    raise AssertionError("the worker pool did not settle")


def _span_events(trace_id):
    return [e for e in ray_tpu.cluster_trace()["events"]
            if e.get("ph") == "X"
            and (e.get("args") or {}).get("trace_id") == trace_id]


def _settled_books(handle):
    """The replica's books once the warm-up is over and the
    controller's word is in."""
    def books():
        b = handle.replica_info.remote().result(timeout=60)["start"]
        return b if b["warm"] is not None and "root" in b else None
    return _wait_for(books)


def _wait_for(fn, timeout=60.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        out = fn()
        if out:
            return out
        time.sleep(0.1)
    raise AssertionError("timed out")


_DISABLED = """
import json, sys, time
import ray_tpu
from ray_tpu import serve
from ray_tpu._private import tracing
from ray_tpu.serve.llm.api import llm_deployment
sys.path.insert(0, {tests!r})
from test_replica_start_trace import ENGINE, _settled_books, _toy_loader

tracing.set_enabled(False)       # this process: driver, GCS, raylet
ray_tpu.init(num_cpus=8, _system_config={{"trace_enabled": False}})
serve.start()
handle = llm_deployment(_toy_loader, engine_config=ENGINE).deploy()
tokens = handle.generate.remote([1, 2, 3], max_new_tokens=2).result(
    timeout=120)
books = _settled_books(handle)
spans = [e["name"] for e in ray_tpu.cluster_trace()["events"]
         if e.get("ph") == "X"]
serve.shutdown()
ray_tpu.shutdown()
print(json.dumps({{"tokens": tokens, "books": books, "spans": spans}}))
"""


def test_with_tracing_disabled_the_books_still_hold_the_seconds():
    """In a process of its own (a system config outlives its cluster):
    every process born with tracing off, the deploy works, no span is
    recorded anywhere, and the books still hold the seconds."""
    import subprocess
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [REPO, os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run(
        [sys.executable, "-c",
         _DISABLED.format(tests=os.path.join(REPO, "tests"))],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    books = got["books"]
    assert len(got["tokens"]) == 2 and got["spans"] == []
    assert books["trace_id"]
    assert books["load"] > 0 and books["build"] > 0 and books["warm"] > 0
    assert abs(sum(books[p] for p in PHASES) + books["unaccounted"]
               - books["root"]) < 1e-3


@pytest.fixture(scope="module")
def started():
    """One toy `llm_deployment` started through `serve`, its warm-up
    over and the controller's word in: the books, every event of the
    cluster's rings, and the assembled tree."""
    ray_tpu.init(num_cpus=8)
    try:
        holders = _hold_the_pool()
        serve.start()
        handle = llm_deployment(_toy_loader, engine_config=ENGINE).deploy()
        books = _settled_books(handle)
        events = ray_tpu.cluster_trace()["events"]
        yield {"books": books, "events": events, "handle": handle,
               "tree": tracing.assemble(events, books["trace_id"]),
               "holders": holders}
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def _by_name(tree):
    out = {}
    for s in tree["spans"]:
        out.setdefault(s["name"], s)
    return out


def test_one_trace_id_on_all_seven_spans(started):
    tree = started["tree"]
    spans = _by_name(tree)
    assert set(SEVEN) <= set(spans)
    assert spans["raylet.worker_start"]["args"]["how"] in ("zygote", "cold")
    assert spans["raylet.worker_start"]["args"]["kind"] == "cpu"
    root = spans["serve.replica_start"]
    assert root["args"]["ok"] is True and root["parent_id"] is None
    assert root["args"]["deployment"] == "llm"
    assert tree["roots"] == [root]
    ids = {s["span_id"] for s in tree["spans"]}
    r0, r1 = root["ts"], root["ts"] + root["dur"]
    for s in tree["spans"]:
        if s is root:
            continue
        assert s["parent_id"] in ids, s["name"]
        # everything but the warm-up, which outlasts the readiness
        # probe, lies inside the root
        assert r0 <= s["ts"], s["name"]
        if not s["name"].startswith("engine.warm") \
                and s["name"] != "jax.compile":
            assert s["ts"] + s["dur"] <= r1 + 1e3, s["name"]
    # the constructor's spans are nested as the calls are
    assert spans["worker.boot"]["parent_id"] == \
        spans["raylet.worker_start"]["span_id"]
    init = spans["serve.replica_init"]
    for name in ("llm.load_model", "engine.build", "engine.warm",
                 "serve.replica_unpickle"):
        assert spans[name]["parent_id"] == init["span_id"]
    assert spans["llm.load_model"]["args"]["param_bytes"] > 0
    assert spans["engine.build"]["args"]["cache_bytes"] > 0
    warm = spans["engine.warm"]
    assert warm["args"]["programs"] == 2
    kids = [c["name"] for c in warm["children"] if c["name"] != "jax.compile"]
    assert kids == ["engine.warm.tick", "engine.warm.chunk"]


def test_phases_and_unaccounted_are_the_root(started):
    books, tree = started["books"], started["tree"]
    assert abs(sum(books[p] for p in PHASES) + books["unaccounted"]
               - books["root"]) < 1e-3
    assert books["unaccounted"] >= 0.0
    assert all(books[p] >= 0.0 for p in PHASES)
    # a fresh worker's boot lies inside the raylet's wait for it, but
    # for the moment between its registration and its being ready
    spans = _by_name(tree)
    boot, wait = spans["worker.boot"], spans["raylet.worker_start"]
    assert wait["ts"] <= boot["ts"]
    inside = (min(boot["ts"] + boot["dur"], wait["ts"] + wait["dur"])
              - boot["ts"]) / 1e6
    assert abs(books["boot"] - inside) < 1e-3
    assert boot["dur"] / 1e6 - inside < 0.5
    assert abs(books["spawn"] + books["boot"]
               - spans["raylet.worker_start"]["dur"] / 1e6) < 1e-3
    assert abs(books["root"]
               - spans["serve.replica_start"]["dur"] / 1e6) < 1e-3
    # ...and the books are what the spans say, key by key
    block = tree["breakdown"]["start"]
    for key in PHASES + ("warm", "warm_after_ready", "unaccounted", "root"):
        assert abs(block[key + "_s"] - books[key]) < 1e-3, key


def test_compiles_lie_inside_load_or_warm(started):
    spans = _by_name(started["tree"])
    pid = spans["engine.warm"]["pid"]
    warm_end = spans["engine.warm"]["ts"] + spans["engine.warm"]["dur"]
    windows = [(spans[n]["ts"], spans[n]["ts"] + spans[n]["dur"])
               for n in ("llm.load_model", "engine.warm")]
    compiles = [e for e in started["events"]
                if e.get("name") == "jax.compile" and e.get("pid") == pid
                and e["ts"] <= warm_end]
    assert compiles
    for e in compiles:
        assert any(t0 <= e["ts"] and e["ts"] + e["dur"] <= t1
                   for t0, t1 in windows), e
    block = started["tree"]["breakdown"]["start"]["compiles"]
    assert len(block) == len(compiles)
    named = {c["fun_name"]: c for c in block}
    assert named["jit(_paged_tick)"]["inside"] == "engine.warm.tick"
    assert named["jit(_prefill_chunk)"]["inside"] == "engine.warm.chunk"
    assert all(c["from_cache"] is False for c in block)


def test_warm_ends_after_ready_and_says_by_how_much(started):
    books, spans = started["books"], _by_name(started["tree"])
    init, warm = spans["serve.replica_init"], spans["engine.warm"]
    after = (warm["ts"] + warm["dur"] - init["ts"] - init["dur"]) / 1e6
    assert after > 0.0
    assert abs(warm["args"]["after_ready_s"] - after) < 1e-3
    assert abs(books["warm_after_ready"] - after) < 1e-3
    assert abs(books["warm"] - warm["dur"] / 1e6) < 1e-3


def test_assemble_and_format_give_the_start_block(started):
    tree = started["tree"]
    assert "ttft" not in tree["breakdown"]
    block = tree["breakdown"]["start"]
    assert set(k + "_s" for k in PHASES + ("warm", "unaccounted")) \
        <= set(block)
    text = tracing.format_trace(tree)
    assert "start " in text and "unaccounted" in text
    assert "compiled" in text and "jit(_paged_tick)" in text
    # a request's trace has no start block
    handle = started["handle"]
    with tracing.span("test", "test.request") as h:
        handle.generate.remote([1, 2, 3], max_new_tokens=2).result(
            timeout=120)
    req = ray_tpu.get_trace(h.trace_id)
    assert "start" not in req["breakdown"]


def test_a_constructor_that_raises_leaves_a_failed_root(started):
    llm_deployment(_failing_loader, name="broken",
                   engine_config=ENGINE).deploy(_blocking=False)

    def failed_root():
        return [e for e in ray_tpu.cluster_trace()["events"]
                if e.get("name") == "serve.replica_start"
                and e["args"]["deployment"] == "broken"]
    root = _wait_for(failed_root, timeout=120)[0]
    assert root["args"]["ok"] is False
    spans = {e["name"]: e for e in _span_events(root["args"]["trace_id"])}
    assert {"raylet.worker_start", "task.create_actor",
            "serve.replica_init", "serve.replica_unpickle",
            "llm.load_model"} <= set(spans)
    assert "no weights here" in spans["llm.load_model"]["args"]["error"]
    assert "error" in spans["serve.replica_init"]["args"]
    assert "engine.build" not in spans and "engine.warm" not in spans
    serve.delete("broken")


@pytest.mark.parametrize("metric", METRICS)
def test_the_metric_files_read_the_books(started, metric, tmp_path):
    """The six files and their reader, through a registry in a temporary
    directory as a later `benchmark` PR's entries would reach them."""
    sys.path.insert(0, os.path.join(BENCH, "tests"))
    try:
        import toy
    finally:
        sys.path.pop(0)
    from benchmarks.lib.registry import Registry

    root = toy.build(str(tmp_path))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["per_layer"] = [
        {"name": m, "unit": "s", "better": "lower",
         "source": "program_span",
         "layer": "Runtime (_private/raylet.py, worker.py)",
         "moves": "setup_s", "workloads": ["mistral7b-doc"]}
        for m in METRICS]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    reg = Registry(root)
    info = started["handle"].replica_info.remote().result(timeout=60)
    phase = metric[len("start_"):-len("_s")]
    got = reg.read_metrics("mistral7b-doc", "per_layer",
                           {"replica_info": info})
    assert set(got) == set(METRICS)
    assert got[metric] == {"value": started["books"][phase], "unit": "s"}
    # a parent commit's replica says nothing of its start
    info.pop("start")
    assert reg.read_metrics("mistral7b-doc", "per_layer",
                            {"replica_info": info}) == {}
    assert reg.read_metrics("mistral7b-doc", "per_layer", {}) == {}


def test_open_backend_names_a_tpu_workers_first_jax_call(monkeypatch):
    """Only a process pinned to `tpu` is opened by name; there the first
    call leaves one `jax.backend_init` span and the second nothing."""
    monkeypatch.setitem(jax_utils._BOUND, "opened", False)
    n = len(tracing.ring())
    jax_utils.open_backend()              # pinned to cpu here: left alone
    assert len(tracing.ring()) == n and not jax_utils._BOUND["opened"]
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    jax_utils.open_backend()
    jax_utils.open_backend()
    new = [e for e in tracing.ring().snapshot()[n:]
           if e["name"] == "jax.backend_init"]
    assert len(new) == 1 and new[0]["args"]["devices"] >= 1
    assert tracing.start_noted("backend_init") is not None


def test_start_seconds_of_a_reused_worker():
    """A worker that booted before the lease was asked has no boot in
    this start; its lease's wait is all `spawn`."""
    iv = {"root": (100.0, 110.0), "spawn": (100.5, 100.6),
          "boot": (90.0, 92.0), "unpickle": (101.0, 102.0),
          "load": (102.0, 105.0), "build": (105.0, 106.0),
          "init": (100.9, 106.5), "warm": (106.4, 109.0)}
    s = tracing.start_seconds(iv)
    assert s["boot"] == 0.0 and s["spawn"] == pytest.approx(0.1)
    assert s["unaccounted"] == pytest.approx(10.0 - 0.1 - 1 - 3 - 1)
    assert s["warm_after_ready"] == pytest.approx(2.5)
    iv.update(spawn=(100.5, 103.0), boot=(100.7, 102.9))
    s = tracing.start_seconds(iv)
    assert s["boot"] == pytest.approx(2.2)
    assert s["spawn"] == pytest.approx(0.3)
    assert tracing.start_seconds({})["unaccounted"] is None
