"""Cross-plane distributed tracing: the per-process span ring
(_private/tracing.py), trace-id continuity across task graphs /
transfer pulls / serve streams, the authoritative dump_trace pull path
(ray_tpu.cluster_trace / rt trace), and the optional OTel export bridge
(util/tracing.py — reference: util/tracing/tracing_helper.py)."""

import time

import pytest

import ray_tpu
from ray_tpu._private import tracing as rt_tracing
from ray_tpu.util import tracing


class FakeSpan:
    def __init__(self, rec):
        self.rec = rec

    def end(self, end_time=None):
        self.rec["end_ns"] = end_time


class FakeTracer:
    def __init__(self):
        self.spans = []

    def start_span(self, name, attributes=None, start_time=None):
        rec = {"name": name, "attributes": dict(attributes or {}),
               "start_ns": start_time}
        self.spans.append(rec)
        return FakeSpan(rec)


def test_export_bridges_profile_events():
    tracer = FakeTracer()
    tracing.enable_tracing(tracer)
    try:
        event = {"cat": "task", "name": "f", "ph": "X",
                 "ts": 1000.0, "dur": 500.0,
                 "args": {"trace_id": "t1", "span_id": "s1",
                          "parent_id": None}}
        tracing.maybe_export(event)
        assert len(tracer.spans) == 1
        span = tracer.spans[0]
        assert span["name"] == "f"
        assert span["attributes"]["ray_tpu.trace_id"] == "t1"
        assert span["start_ns"] == 1_000_000
        assert span["end_ns"] == 1_500_000
    finally:
        tracing.disable_tracing()
    tracing.maybe_export(event)
    assert len(tracer.spans) == 1  # disabled -> no-op


def test_worker_execution_emits_spans():
    """A task executed in a traced process flows through the bridge:
    enable tracing inside the worker via the task itself."""
    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    try:
        @ray_tpu.remote
        def traced_then_probe():
            from ray_tpu._private import worker as worker_mod
            from ray_tpu.util import tracing as tr

            class Counter:
                def __init__(self):
                    self.n = 0

                def start_span(self, name, attributes=None,
                               start_time=None):
                    self.n += 1

                    class S:
                        def end(self, end_time=None):
                            pass
                    return S()

            c = Counter()
            tr.enable_tracing(c)
            # Record an event directly through the worker's profiler.
            worker_mod.global_worker._record_profile_event(
                "task", "probe", 0.0,
                trace={"trace_id": "x", "span_id": "y",
                       "parent_id": None})
            tr.disable_tracing()
            return c.n

        assert ray_tpu.get(traced_then_probe.remote(), timeout=60) == 1
    finally:
        ray_tpu.shutdown()


def test_export_carries_otel_links_when_available():
    """A tracer accepting links= gets the parent id as a REAL link
    (SpanContext from the propagated hex ids); tracer-shaped doubles
    without the kwarg keep working through the attribute fallback
    (test_export_bridges_profile_events above)."""

    class LinkTracer(FakeTracer):
        def start_span(self, name, attributes=None, start_time=None,
                       links=None):
            rec = {"name": name, "attributes": dict(attributes or {}),
                   "start_ns": start_time, "links": links}
            self.spans.append(rec)
            return FakeSpan(rec)

    try:
        import opentelemetry  # noqa: F401
        has_otel = True
    except ImportError:
        has_otel = False
    tracer = LinkTracer()
    tracing.enable_tracing(tracer)
    try:
        tracing.maybe_export(
            {"cat": "task", "name": "f", "ph": "X", "ts": 1.0,
             "dur": 2.0,
             "args": {"trace_id": "ab" * 8, "span_id": "cd" * 8,
                      "parent_id": "ef" * 8}})
    finally:
        tracing.disable_tracing()
    (span,) = tracer.spans
    assert span["attributes"]["ray_tpu.parent_id"] == "ef" * 8
    if has_otel:
        (link,) = span["links"]
        assert link.context.trace_id == int("ab" * 8, 16)
        assert link.context.span_id == int("ef" * 8, 16)
    else:
        assert span["links"] is None  # attribute-only fallback


# ---------------------------------------------------------------------------
# The span ring (always-on flight recorder)


def test_ring_overflow_drops_oldest_and_counts():
    ring = rt_tracing.TraceRing(capacity=8)
    for i in range(20):
        ring.append({"name": f"e{i}", "ts": float(i)})
    assert len(ring) == 8
    assert ring.dropped == 12
    kept = [e["name"] for e in ring.snapshot()]
    assert kept == [f"e{i}" for i in range(12, 20)]  # oldest went first
    stats = ring.stats()
    assert stats["dropped"] == 12 and stats["depth"] == 8
    assert stats["ts_min"] == 12.0 and stats["ts_max"] == 19.0


def test_meta_event_self_describes_truncation():
    """The dump/timeline meta event names what the ring could NOT
    retain: drop count + coverage window — a truncated trace reads as
    truncated, not as 'nothing else happened'."""
    ring = rt_tracing.TraceRing(capacity=4)
    for i in range(10):
        ring.append({"name": "x", "ts": float(i)})
    me = rt_tracing.meta_event(dict(ring.stats(), pid=1234))
    assert me["name"] == "trace.ring_meta" and me["ph"] == "i"
    assert me["args"]["events_dropped"] == 6
    assert me["args"]["ring_capacity"] == 4
    assert me["args"]["window_start_ts"] == 6.0
    assert me["pid"] == 1234


def test_record_disabled_is_noop(monkeypatch):
    ring = rt_tracing.TraceRing(capacity=64)
    monkeypatch.setattr(rt_tracing, "_RING", ring)
    rt_tracing.set_enabled(False)
    try:
        rt_tracing.record("task", "x", time.time(), 0.1)
        rt_tracing.event("task", "x")
        rt_tracing.flow_start("f1")
        assert len(ring) == 0
    finally:
        rt_tracing.set_enabled(True)
    rt_tracing.record("task", "x", time.time(), 0.1)
    assert len(ring) == 1


def test_min_dur_gate_keeps_linked_spans(monkeypatch):
    """The noise gate drops only UNLINKED blips — dropping a span that
    carries trace linkage would hole the request tree."""
    # The gate reads the config object `tracing` holds: the one that was
    # `GLOBAL_CONFIG` when the module was imported.  An `rt.init(
    # _system_config=...)` in an earlier test of this worker REBINDS
    # `config.GLOBAL_CONFIG` (config.apply_system_config), so importing
    # the name here patches an object the gate never looks at: what made
    # this test red under six workers and green alone.
    ring = rt_tracing.TraceRing(capacity=64)
    monkeypatch.setattr(rt_tracing, "_RING", ring)
    monkeypatch.setattr(rt_tracing.cfg, "trace_min_dur_us", 1000.0)

    def mine():
        # (by name: a thread an earlier test left behind may record
        # into the swapped-in module global too)
        return [e["name"] for e in ring.snapshot()
                if e.get("name") in ("blip", "linked")]

    rt_tracing.record("task", "blip", time.time(), 0.0001)
    assert mine() == []
    rt_tracing.record("task", "linked", time.time(), 0.0001,
                      trace={"trace_id": "t", "span_id": "s",
                             "parent_id": None})
    assert mine() == ["linked"]


def test_drop_counter_exported_to_prometheus(monkeypatch):
    """tracing_events_dropped_total reaches the prometheus surface,
    and moves ONLY when the ring actually overflowed."""
    from ray_tpu.util.metrics import prometheus_text, registry_snapshot

    def _counter_value():
        for s in registry_snapshot():
            if s["name"] == "tracing_events_dropped_total":
                return sum(s["values"].values())
        return 0.0

    ring = rt_tracing.TraceRing(capacity=4)
    monkeypatch.setattr(rt_tracing, "_RING", ring)
    monkeypatch.setattr(rt_tracing, "_exported_drops", 0)
    rt_tracing.export_metrics()  # no overflow -> no counter movement
    before = _counter_value()
    for i in range(10):
        rt_tracing.record("task", "x", time.time(), 0.1)
    assert ring.dropped == 6
    rt_tracing.export_metrics()
    after = _counter_value()
    assert after - before == 6.0
    text = prometheus_text(registry_snapshot())
    assert "tracing_events_dropped_total" in text
    assert "tracing_ring_depth" in text


def test_telemetry_kv_push_respects_byte_budget():
    """The periodic telemetry KV push is the STALE convenience view and
    must stay control-plane-sized: a full 8k ring pickles to hundreds
    of KiB, which belongs on the dump_trace pull.  The push halves its
    profile tail until the payload fits cfg.trace_kv_push_budget,
    keeping the freshest events and the full-ring stats."""
    import pickle
    import types

    from ray_tpu._private.config import GLOBAL_CONFIG as cfg
    from ray_tpu._private.worker import CoreWorker

    ring = rt_tracing.TraceRing(capacity=8192)
    for i in range(4000):
        ring.append({"cat": "task", "name": f"span-{i}", "ph": "X",
                     "pid": 1, "tid": 1, "ts": float(i), "dur": 5.0,
                     "args": {"pad": "v" * 40}})
    stub = types.SimpleNamespace(_trace_ring=ring, mode="worker")
    payload = CoreWorker._telemetry_payload(stub, [])
    assert payload is not None
    assert len(payload) <= cfg.trace_kv_push_budget
    data = pickle.loads(payload)
    # Freshest tail survives the shrink; stats still describe the ring.
    assert data["profile"] and data["profile"][-1]["name"] == "span-3999"
    assert data["trace_stats"]["depth"] == 4000
    # Nothing to push -> no KV write at all.
    empty = types.SimpleNamespace(
        _trace_ring=rt_tracing.TraceRing(capacity=8), mode="worker")
    assert CoreWorker._telemetry_payload(empty, []) is None


# ---------------------------------------------------------------------------
# Span tree assembly + breakdown (rt trace)


def _mk(name, cat, pid, ts, dur, tid, sid, parent):
    return {"cat": cat, "name": name, "ph": "X", "pid": pid,
            "tid": 1, "ts": ts, "dur": dur,
            "args": {"trace_id": tid, "span_id": sid,
                     "parent_id": parent}}


def test_assemble_links_spans_and_derives_ttft():
    events = [
        _mk("serve.request", "serve", 1, 0.0, 500e3, "T", "a", None),
        _mk("engine.queue", "engine", 2, 10e3, 100e3, "T", "b", "a"),
        _mk("engine.prefill", "engine", 2, 110e3, 50e3, "T", "c", "a"),
        _mk("engine.first_tick", "engine", 2, 160e3, 10e3, "T", "d",
            "a"),
        _mk("other.trace", "task", 3, 0.0, 1.0, "U", "z", None),
        {"cat": "serve", "name": "serve.failover", "ph": "i", "s": "p",
         "pid": 1, "tid": 1, "ts": 200e3,
         "args": {"trace_id": "T", "parent_id": "a",
                  "replica_died": "r#1"}},
    ]
    tree = rt_tracing.assemble(events, "T")
    assert tree["processes"] == [1, 2]
    (root,) = tree["roots"]
    assert root["name"] == "serve.request"
    assert [c["name"] for c in root["children"]] == [
        "engine.queue", "engine.prefill", "engine.first_tick"]
    # The failover annotation attaches to its parent span.
    assert root["events"][0]["name"] == "serve.failover"
    bd = tree["breakdown"]
    assert bd["ttft"]["queue_ms"] == 100.0
    assert bd["ttft"]["prefill_ms"] == 50.0
    assert bd["ttft"]["first_tick_ms"] == 10.0
    assert bd["ttft"]["ttft_ms"] == 160.0
    text = rt_tracing.format_trace(tree)
    assert "TTFT" in text and "serve.request" in text
    assert "2 process(es)" in text
    # The other trace's span stayed out.
    assert "other.trace" not in text
    ids = rt_tracing.trace_ids(events)
    assert set(ids) == {"T", "U"}
    assert ids["T"][0] == 5  # 4 spans + 1 annotation


# ---------------------------------------------------------------------------
# Trace-id continuity across the planes (the acceptance criterion)


def test_task_graph_one_trace_id_flow_connected(ray_start_cluster):
    """driver span → task → nested task → actor call → remote get
    (transfer-plane pull): ONE trace id end to end, chrome flow
    events (ph s/f) connect the cross-process edges, and the
    authoritative cluster_trace() pull assembles the tree."""
    import numpy as np
    cluster = ray_start_cluster
    cluster.add_node(num_cpus=2)
    b = cluster.add_node(num_cpus=2, resources={"B": 2})
    cluster.wait_for_nodes(2)
    cluster.connect()

    @ray_tpu.remote
    class Acc:
        def bump(self, x):
            return x + 1

    @ray_tpu.remote(resources={"B": 1})
    def produce():
        # Big enough to live in the remote store: the driver-side get
        # crosses worker -> raylet -> transfer pull.
        return np.ones(2 * 1024 * 1024, np.uint8)

    @ray_tpu.remote
    def nested(x):
        return x * 2

    @ray_tpu.remote
    def outer(acc):
        # Deliberately nested gets: the POINT is the span nesting a
        # nested task graph produces (4 free CPUs, no pool deadlock).
        v = ray_tpu.get(nested.remote(3), timeout=60)  # noqa: RTL004
        return ray_tpu.get(acc.bump.remote(v),  # noqa: RTL004
                           timeout=60)

    acc = Acc.remote()
    with rt_tracing.span("app", "test_root") as h:
        assert ray_tpu.get(outer.remote(acc), timeout=120) == 7
        blob = ray_tpu.get(produce.remote(), timeout=120)
        tid = h.trace_id
    assert blob.nbytes == 2 * 1024 * 1024

    out = ray_tpu.cluster_trace()
    events = out["events"]
    mine = rt_tracing.trace_events(events, tid)
    names = {e["name"] for e in mine}
    assert {"test_root", "outer", "nested", "bump",
            "transfer.pull"} <= names, names
    # One trace, several processes: at least driver + 2 workers.
    pids = {e["pid"] for e in mine if e.get("ph") == "X"}
    assert len(pids) >= 3, pids
    # Flow edges connect: every execution span carrying a flow id has
    # a matching start (submit site) and finish (exec site) event.
    flows = {e["args"]["flow"] for e in mine
             if e.get("args", {}).get("flow")}
    assert flows
    starts = {e["id"]: e["pid"] for e in events if e.get("ph") == "s"}
    ends = {e["id"]: e["pid"] for e in events if e.get("ph") == "f"}
    connected = [f for f in flows if f in starts and f in ends]
    assert connected, (flows, len(starts), len(ends))
    # At least one edge truly crosses processes.
    assert any(starts[f] != ends[f] for f in connected)
    # Assembly: the tree roots at the driver span and reaches the
    # task spans as descendants.
    tree = rt_tracing.assemble(events, tid)
    root = next(r for r in tree["roots"] if r["name"] == "test_root")

    def _names(s):
        yield s["name"]
        for c in s["children"]:
            yield from _names(c)
    assert {"outer", "nested"} <= set(_names(root))

    # The stats-only pull (rt status's trace-ring table) reports every
    # process's ring health without shipping events.
    stats = ray_tpu.cluster_trace(stats_only=True)["processes"]
    assert all("events" not in p for p in stats)
    assert any(p.get("depth", 0) > 0 for p in stats)
    # timeline() stays the lagging convenience view, but is now
    # self-describing: ring meta events ride along.
    tl = ray_tpu.timeline()
    assert any(e["name"] == "trace.ring_meta" for e in tl)


# ---------------------------------------------------------------------------
# Serve request lifecycle traces (proxy → router → replica → engine)


def _llm_fixture_bits():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import gpt
    cfg = gpt.GPTConfig(vocab_size=97, d_model=32, n_heads=4,
                        n_layers=2, d_ff=64, max_seq=64,
                        dtype=jnp.float32, remat=False, use_flash=False)

    def loader(_cfg=cfg):
        return gpt.init_params(_cfg, jax.random.PRNGKey(0)), _cfg

    def prompt(seed, n):
        return [int(t) for t in np.asarray(jax.random.randint(
            jax.random.PRNGKey(seed), (n,), 1, cfg.vocab_size))]

    return loader, prompt


@pytest.fixture
def serve_session():
    from ray_tpu import serve
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    serve.start()
    yield
    serve.shutdown()
    ray_tpu.shutdown()


def test_serve_stream_trace_crosses_processes_with_ttft(serve_session):
    """The serve acceptance shape: a streamed generation traced from
    the client span shows a connected tree crossing the driver and the
    replica process, with the TTFT decomposition (queue vs prefill vs
    first tick) derived from the engine's stage spans."""
    from ray_tpu.serve.llm.api import llm_deployment
    loader, prompt = _llm_fixture_bits()
    handle = llm_deployment(
        loader, name="traced_llm", num_replicas=1,
        engine_config=dict(num_slots=2, max_seq=40,
                           prefill_chunk=4)).deploy()
    with rt_tracing.span("app", "client_request") as h:
        toks = list(handle.options("stream").stream(
            prompt(0, 8), max_new_tokens=6))
        tid = h.trace_id
    assert len(toks) == 6

    tree = ray_tpu.get_trace(tid)
    names = {s["name"] for s in tree["spans"]}
    assert {"client_request", "serve.qos_wait", "serve.assign",
            "serve.replica_stream", "engine.queue", "engine.prefill",
            "engine.first_tick"} <= names, names
    assert len(tree["processes"]) >= 2  # driver + replica worker
    bd = tree["breakdown"]["ttft"]
    assert bd["ttft_ms"] == pytest.approx(
        bd["queue_ms"] + bd["prefill_ms"] + bd["first_tick_ms"],
        abs=0.01)
    assert bd["ttft_ms"] > 0
    # Render path (rt trace) carries the breakdown line.
    assert "TTFT" in rt_tracing.format_trace(tree)


@pytest.mark.slow  # in `make chaos` explicitly; keeps tier-1 lean
def test_serve_failover_stream_keeps_one_trace_id(serve_session):
    """Kill the replica serving a traced greedy stream: the resumed
    stream's spans carry the ORIGINAL trace id (annotated with a
    serve.failover event), and spans from BOTH replica processes land
    in the one tree."""
    from ray_tpu.serve.llm.api import llm_deployment
    loader, prompt = _llm_fixture_bits()
    handle = llm_deployment(
        loader, name="traced_fo", num_replicas=2,
        engine_config=dict(num_slots=2, max_seq=40,
                           prefill_chunk=4)).deploy()
    sub = handle.options("stream")
    with rt_tracing.span("app", "client_request") as h:
        stream = sub.stream(prompt(0, 8), max_new_tokens=24)
        got = []
        it = iter(stream)
        for _ in range(5):
            got.append(next(it))
        rs = sub._router.replica_set
        tag = next(t for t, n in rs._in_flight.items() if n > 0)
        actor = next(r["actor"] for r in rs._replicas
                     if r["replica_tag"] == tag)
        ray_tpu.kill(actor)
        got.extend(it)  # failover happens inside the iterator
        tid = h.trace_id
    assert len(got) == 24

    events = ray_tpu.cluster_trace()["events"]
    mine = rt_tracing.trace_events(events, tid)
    # The failover annotation rides the trace, naming the dead replica.
    fo = [e for e in mine if e["name"] == "serve.failover"]
    assert fo and fo[0]["args"]["replica_died"] == tag
    # The client consumed 5 items before the kill, but the replica may
    # have pushed a few more into the router's buffer before dying —
    # "delivered" counts the router's receipts, so it is >= 5 and is
    # the exact resume point (len(got) == 24 above proves no token was
    # lost or duplicated across the failover).
    delivered = fo[0]["args"]["delivered"]
    assert delivered >= 5
    # Both assignment attempts live in the driver's ring under the ONE
    # trace id: the original replica and the failover target.  (The
    # dead replica's own ring died with its process — the flight
    # recorder is per-process by design; its spans are the documented
    # loss on SIGKILL.)
    assigns = [e for e in mine if e["name"] == "serve.assign"]
    assert {a["args"]["replica"] for a in assigns} >= {tag}
    assert len(assigns) >= 2, assigns
    assert any(a["args"]["failover"] == 1
               and a["args"]["resumed"] == delivered for a in assigns)
    # The SURVIVOR's resumed generation carries the original trace id:
    # its engine stage spans are in the tree.
    survivor_engine = [e for e in mine
                       if e["name"].startswith("engine.")]
    assert survivor_engine, "resumed replica's spans lost the trace id"
    assert {"engine.queue", "engine.prefill", "engine.first_tick"} <= \
        {e["name"] for e in survivor_engine}


@pytest.mark.slow  # real HTTP wire; in `make chaos` via the SSE leg
def test_http_sse_trace_header_links_client_proxy_replica(
        serve_session):
    """The ≥3-process acceptance: a driver-side span rides the
    x-rt-trace header through the HTTP proxy (its own actor process) to
    the replica; the response echoes x-rt-trace-id and the assembled
    tree spans client, proxy, and replica processes with the TTFT
    breakdown."""
    import json

    import requests

    from ray_tpu import serve
    from ray_tpu.serve.llm.api import llm_deployment
    loader, prompt = _llm_fixture_bits()
    llm_deployment(loader, name="traced_http", num_replicas=1,
                   engine_config=dict(num_slots=2, max_seq=40,
                                      prefill_chunk=4)).deploy()
    serve.run(serve.get_deployment("traced_http"), _start_proxy=True)
    addr = serve.get_proxy_address()
    url = f"http://{addr['host']}:{addr['port']}/traced_http"
    with rt_tracing.span("app", "http_client") as h:
        r = requests.post(
            url, json={"tokens": prompt(0, 8), "max_new_tokens": 5},
            headers={"Accept": "text/event-stream",
                     "x-rt-trace": f"{h.trace_id}:{h.span_id}"},
            timeout=120)
        tid = h.trace_id
    assert r.status_code == 200
    assert r.headers.get("x-rt-trace-id") == tid
    toks = [json.loads(ln[6:])["token"] for ln in r.text.splitlines()
            if ln.startswith("data: ") and "[DONE]" not in ln]
    assert len(toks) == 5

    tree = ray_tpu.get_trace(tid)
    names = {s["name"] for s in tree["spans"]}
    assert {"http_client", "serve.request", "serve.replica_stream",
            "engine.prefill"} <= names, names
    # client (driver), proxy actor, replica actor: >= 3 processes.
    assert len(tree["processes"]) >= 3, tree["processes"]
    assert tree["breakdown"]["ttft"]["ttft_ms"] > 0
