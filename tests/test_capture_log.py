"""The engine loop's capture log (PR 61) and the reader that joins it to
the device's idle gaps.

While a profiler capture runs, `GenerationEngine._phase` keeps every
phase it closes and `_mark` every program the loop hands to the device,
on the monotonic clock, in a bounded buffer outside the ring;
`capture_events()` / `LLMServer.trace_spans()` hand them out as
`engine.phase.<name>`, `engine.dispatch` and `engine.capture_log`
events.  `benchmarks/readers/idle_by_phase.py` fits the one offset
between that log and a capture's `gap_events` and puts each idle gap of
the chip down to what the host was doing.
"""

import json
import os
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu._private import tracing
from ray_tpu.models import gpt
from ray_tpu.serve.llm import engine as engine_mod
from ray_tpu.serve.llm.api import LLMServer
from ray_tpu.serve.llm.engine import LOOP_PHASES, GenerationEngine
from ray_tpu.util import tpu_profiler

GPT_CFG = gpt.GPTConfig(vocab_size=97, d_model=32, n_heads=4,
                        n_layers=2, d_ff=64, max_seq=64,
                        dtype=jnp.float32, remat=False, use_flash=False)
ENGINE_KW = dict(num_slots=3, max_seq=48, prefill_chunk=5, page_size=4,
                 kv_pages=40)
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _engine(name, **kw):
    params = gpt.init_params(GPT_CFG, jax.random.PRNGKey(0))
    return GenerationEngine(params, GPT_CFG, name=name,
                            **{**ENGINE_KW, **kw})


def _prompt(seed, n, vocab=97):
    return [int(t) for t in np.random.default_rng(seed).integers(
        1, vocab, size=n)]


def _settle(eng):
    eng.run_on_worker(lambda: None)
    eng.run_on_worker(lambda: None)


def _traffic(eng, seed=0, n=3, new=24):
    """A few requests that overlap: each joins while the one before it
    decodes (its prompt leaves whole pages behind in the prefix tree)."""
    streams = []
    for j in range(n):
        streams.append(eng.submit(_prompt(seed + j, 17 + j),
                                  max_new_tokens=new))
        next(iter(streams[-1]))       # it decodes before the next joins
    for st in streams:
        assert len(st.result(timeout=120)) == new - 1
    _settle(eng)


def _split(events):
    phases = [e for e in events if e["name"].startswith("engine.phase.")]
    marks = [e for e in events if e["name"] == "engine.dispatch"]
    own = [e for e in events if e["name"] == "engine.capture_log"]
    return phases, marks, own


@pytest.fixture
def captured(tmp_path, monkeypatch):
    """One capture over a running engine with tiering on: traffic, a
    forced sweep that moves pages, an idle stretch; the capture stopped
    and the loop taken through one more switch."""
    cfg = engine_mod._cfg     # the object the engine reads
    monkeypatch.setattr(cfg, "serve_kv_demote_idle_s", 0.0)
    monkeypatch.setattr(cfg, "serve_kv_tier_sweep_s", 3600.0)
    with _engine("caplog", kv_tiering=True) as eng:
        eng.submit(_prompt(1, 6), max_new_tokens=3).result(timeout=120)
        _settle(eng)
        t0 = time.time()
        tpu_profiler.start(str(tmp_path / "cap"))
        try:
            _traffic(eng, seed=10)
            time.sleep(0.15)
            eng.run_on_worker(
                lambda: eng._maybe_sweep_tiers(force=True))

            def caplog_fresh(v):      # a program that has to compile
                return (v * 3.5 + 1.0).sum()

            eng.run_on_worker(lambda: jax.jit(caplog_fresh)(
                jnp.arange(7.0)).block_until_ready())
            _traffic(eng, seed=20, n=2)
        finally:
            tpu_profiler.stop()
        t1 = time.time()
        _settle(eng)                  # the switch that sees the flag down
        yield eng, eng.capture_events(), (t0, t1)


# ---------------------------------------------------------------------------
# The engine's side


def test_no_capture_no_log_and_no_annotation(monkeypatch):
    """With no capture a switch appends nothing and builds no
    annotation object."""
    built = []
    monkeypatch.setattr(engine_mod._tpu_profiler, "annotate",
                        lambda name: built.append(name))
    with _engine("caplog-off", kv_tiering=False) as eng:
        _traffic(eng)
        assert eng.stats().loop_turns > 10
        assert eng.capture_events() == []
        assert eng._cap_phases == [] and eng._cap_marks == []
        assert not eng._cap_on and eng._phase_ann is None
    assert built == []


def test_phases_abut_and_idle_is_among_them(captured):
    _, events, (t0, t1) = captured
    phases, _, own = _split(events)
    assert len(own) == 1 and own[0]["args"]["phases"] == len(phases)
    assert own[0]["args"]["dropped"] == 0 and not own[0]["args"]["open"]
    names = {e["name"][len("engine.phase."):] for e in phases}
    assert names <= set(LOOP_PHASES)
    assert {"idle", "commands", "sweep", "admit", "prefill_dispatch",
            "tick_dispatch", "device_wait", "emit"} <= names
    # a partition of the thread's time from the first to the last: each
    # entry starts where the one before it ended (1 us: epoch floats)
    for a, b in zip(phases, phases[1:]):
        assert abs(a["ts"] + a["dur"] - b["ts"]) <= 1.0, (a, b)
        assert a["name"] != b["name"]
    assert all(e["dur"] >= 0 and e["ph"] == "X" for e in phases)
    turns = [e["args"]["turn"] for e in phases]
    assert turns == sorted(turns) and turns[-1] > turns[0]
    # on the epoch, around the capture
    assert t0 - 0.2 <= phases[0]["ts"] / 1e6 <= t1
    assert t0 <= (phases[-1]["ts"] + phases[-1]["dur"]) / 1e6


def test_every_mark_lies_in_a_dispatch_or_sweep_phase(captured):
    eng, events, _ = captured
    phases, marks, own = _split(events)
    assert own[0]["args"]["dispatches"] == len(marks)
    programs = {m["args"]["program"] for m in marks}
    assert programs == {"_prefill_chunk", "_paged_tick", "_merge_tokens",
                        "paged_read_pages", "convert_element_type"}
    starts = [p["ts"] for p in phases]
    allowed = {"_prefill_chunk": {"prefill_dispatch"},
               "convert_element_type": {"prefill_dispatch"},
               "_paged_tick": {"tick_dispatch"},
               "_merge_tokens": {"tick_dispatch"},
               "paged_read_pages": {"sweep"}}
    for m in marks:
        assert m["ph"] == "i" and set(m["args"]) == {"program"}
        i = int(np.searchsorted(starts, m["ts"], side="right")) - 1
        inside = phases[i]["name"][len("engine.phase."):]
        assert phases[i]["ts"] <= m["ts"] <= \
            phases[i]["ts"] + phases[i]["dur"] + 1.0
        assert inside in allowed[m["args"]["program"]], (m, inside)
    # one tick mark a tick_dispatch phase that dispatched a tick, and
    # a dense chunk's one eager scalar (its position) before the chunk
    count = {p: sum(m["args"]["program"] == p for m in marks)
             for p in programs}
    assert 0 < count["_paged_tick"] <= sum(
        p["name"].endswith("tick_dispatch") for p in phases)
    assert count["convert_element_type"] == count["_prefill_chunk"] > 0


def test_the_phase_open_at_stop_is_closed_and_present(tmp_path):
    with _engine("caplog-open", kv_tiering=False) as eng:
        eng.submit(_prompt(1, 6), max_new_tokens=3).result(timeout=120)
        tpu_profiler.start(str(tmp_path / "cap"))
        try:
            _traffic(eng, n=1)
            time.sleep(0.25)          # the loop idles with the flag up
            before = time.time()
            live = eng.capture_events()
        finally:
            tpu_profiler.stop()
        # asked from this thread mid-capture: the idle wait still open
        # is reported up to now, from a copy
        phases, _, own = _split(live)
        assert own[0]["args"]["open"]
        assert phases[-1]["name"] == "engine.phase.idle"
        assert abs((phases[-1]["ts"] + phases[-1]["dur"]) / 1e6
                   - before) < 0.05
        assert eng._cap_on            # no switch has seen the flag down
        time.sleep(0.05)
        _settle(eng)
        after = time.time()
        phases, _, own = _split(eng.capture_events())
        assert not own[0]["args"]["open"] and not eng._cap_on
        # closed at the switch that saw the flag down, and kept
        assert phases[-1]["name"] == "engine.phase.idle"
        end = (phases[-1]["ts"] + phases[-1]["dur"]) / 1e6
        assert before < end <= after
        n = len(phases)
        _traffic(eng, n=1)            # no capture: the log rests
        assert len(_split(eng.capture_events())[0]) == n
        assert eng._phase_ann is None


def test_a_second_capture_clears_the_first(tmp_path):
    with _engine("caplog-twice", kv_tiering=False) as eng:
        eng.submit(_prompt(1, 6), max_new_tokens=3).result(timeout=120)
        logs = []
        for i in range(2):
            tpu_profiler.start(str(tmp_path / f"cap{i}"))
            try:
                _traffic(eng, seed=30 * i, n=1 + 2 * i)
            finally:
                tpu_profiler.stop()
            _settle(eng)
            logs.append(eng.capture_events())
            time.sleep(0.02)
    (p0, m0, own0), (p1, m1, own1) = _split(logs[0]), _split(logs[1])
    assert len(m1) > len(m0) > 0
    assert own1[0]["args"]["dispatches"] == len(m1)
    # nothing of the first is in the second, and the clock pair is new
    assert p1[0]["ts"] >= p0[-1]["ts"] + p0[-1]["dur"] - 0.2e6
    assert min(m["ts"] for m in m1) > max(m["ts"] for m in m0)
    assert own1[0]["args"]["clock_monotonic_s"] > \
        own0[0]["args"]["clock_monotonic_s"]
    # one pair a capture maps every entry: epoch - monotonic is one number
    a = own1[0]["args"]
    assert abs((a["clock_epoch_s"] - a["clock_monotonic_s"])
               - (time.time() - time.monotonic())) < 0.05


def test_overflow_is_counted_not_dropped_in_silence(tmp_path, monkeypatch):
    monkeypatch.setattr(engine_mod, "_CAPTURE_LOG_ROOM", 25)
    with _engine("caplog-full", kv_tiering=False) as eng:
        eng.submit(_prompt(1, 6), max_new_tokens=3).result(timeout=120)
        tpu_profiler.start(str(tmp_path / "cap"))
        try:
            _traffic(eng)
        finally:
            tpu_profiler.stop()
        _settle(eng)
        phases, marks, own = _split(eng.capture_events())
    assert len(phases) + len(marks) == 25
    assert own[0]["args"]["dropped"] > 25


def test_a_verify_tick_is_marked_under_its_own_name(tmp_path, monkeypatch):
    # a draft every tick, whatever the tokens (a seeded model repeats
    # nothing): right or wrong, it is verified
    monkeypatch.setattr(engine_mod, "_lookup_draft",
                        lambda req, ngram, k: [1] * k)
    with _engine("caplog-spec", kv_tiering=False, speculate_k=2,
                 speculate_ngram=2) as eng:
        rep = [5, 6, 7, 8] * 3
        eng.submit(rep, max_new_tokens=4).result(timeout=120)
        tpu_profiler.start(str(tmp_path / "cap"))
        try:
            assert len(eng.submit(rep, max_new_tokens=16
                                  ).result(timeout=120)) == 16
            _settle(eng)
        finally:
            tpu_profiler.stop()
        _settle(eng)
        phases, marks, _ = _split(eng.capture_events())
        drafted = eng.stats().spec_drafted_tokens
    programs = {m["args"]["program"] for m in marks}
    assert drafted > 0
    assert programs == {"_prefill_chunk", "_paged_verify",
                        "convert_element_type"}
    starts = [p["ts"] for p in phases]
    for m in marks:
        i = int(np.searchsorted(starts, m["ts"], side="right")) - 1
        assert phases[i]["name"].endswith("_dispatch")


def test_trace_spans_returns_the_ring_as_before_and_the_log_behind_it(
        captured):
    eng, events, _ = captured
    server = types.SimpleNamespace(engine=eng)
    spans = LLMServer.trace_spans(server)
    ring = [e for e in tracing.ring().snapshot(clear=False)
            if str(e.get("name", "")).startswith("engine.")]
    n_log = len(events)
    # the ring's spans first, unchanged; then the log
    assert spans[:len(ring)] == ring
    assert [e["name"] for e in spans[len(ring):len(ring) + n_log]] == \
        [e["name"] for e in events]
    names = [e["name"] for e in ring]
    assert names.count("engine.queue") >= 6
    assert names.count("engine.first_tick") >= 6
    assert not any(n.startswith(("engine.phase.", "engine.dispatch",
                                 "engine.capture_log")) for n in names)
    assert len(spans) == len(ring) + n_log
    # the stages of a compile that ended inside the log are part of it
    # (the sweep's gather compiled under the capture), from the
    # listener's own books and not from the ring
    log_t0 = events[-1]["ts"]
    compiles = [e for e in events if e["name"] == "engine.compile"]
    assert all(e["ts"] + e["dur"] >= log_t0 and e["ph"] == "X"
               and set(e["args"]) == {"stage", "fun_name"}
               for e in compiles)
    assert {"backend_compile_duration"} <= {
        e["args"]["stage"] for e in compiles
        if "caplog_fresh" in e["args"]["fun_name"]}
    assert events[-1]["name"] == "engine.capture_log"
    # the shape of a ring event, and nothing json cannot carry
    for e in events:
        assert {"cat", "name", "ph", "pid", "tid", "ts"} <= set(e)
    json.dumps(events)
    # another prefix selects as before
    assert LLMServer.trace_spans(server, prefix="engine.queue") == \
        [e for e in ring if e["name"].startswith("engine.queue")]


def test_the_log_agrees_with_the_host_planes_annotations(tmp_path):
    """Count for count, the capture's host plane (the profiler's own
    clock) holds one `engine.<phase>` region a logged phase but `idle`
    that was opened under the capture."""
    from jax.profiler import ProfileData
    import glob
    with _engine("caplog-plane", kv_tiering=False) as eng:
        eng.submit(_prompt(1, 6), max_new_tokens=3).result(timeout=120)
        _settle(eng)
        d = tpu_profiler.start(str(tmp_path / "cap"))
        try:
            _traffic(eng)
            time.sleep(0.12)
            _settle(eng)
        finally:
            tpu_profiler.stop()
        _settle(eng)
        phases, _, _ = _split(eng.capture_events())
    path = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    plane = {}
    for pl in ProfileData.from_file(path).planes:
        for line in pl.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    plane[e.name] = plane.get(e.name, 0) + 1
    logged = {}
    # the first entry was open before the flag was seen: no region; the
    # last was closed by the switch that saw it down: its region was cut
    for e in phases[1:]:
        n = "engine." + e["name"][len("engine.phase."):]
        logged[n] = logged.get(n, 0) + 1
    logged.pop("engine.idle")
    for name, n in logged.items():
        assert abs(plane.get(name, 0) - n) <= 1, (name, plane, logged)
    assert "engine.idle" not in plane


# ---------------------------------------------------------------------------
# benchmarks/readers/idle_by_phase.py on hand-made gaps and logs, through
# the registry as the harness reads a metric

EPOCH = 1_791_000_000.0
US = 1e-6
CATEGORIES = ("idle", "commands", "sweep", "admit", "prefill_dispatch",
              "tick_dispatch", "device_wait", "emit", "compile", "launch",
              "queued", "unseen")


def _metric(obs, name="idle_host_work_share.tput"):
    from benchmarks.lib.registry import Registry
    reg = Registry(ROOT)
    spec = reg.metric(name)
    assert spec["reader"] == "idle_by_phase"
    return reg.reader(spec["reader"])(obs, **spec.get("args", {}))


def _events(phases, marks, compiles=()):
    """What trace_spans() hands out for a log on the epoch (seconds in,
    the ring's us out), behind two ring spans of a request."""
    out = [{"name": "engine.queue", "ph": "X", "ts": EPOCH * 1e6, "dur": 5.0},
           {"name": "engine.first_tick", "ph": "X", "ts": EPOCH * 1e6,
            "dur": 9.0}]
    out += [{"name": "engine.phase." + n, "ph": "X", "ts": a * 1e6,
             "dur": (b - a) * 1e6, "args": {"turn": i}}
            for i, (n, a, b) in enumerate(phases)]
    out += [{"name": "engine.dispatch", "ph": "i", "ts": t * 1e6,
             "args": {"program": p}} for p, t in marks]
    out += [{"name": "engine.compile", "ph": "X", "ts": a * 1e6,
             "dur": (b - a) * 1e6, "args": {"stage": "backend_compile"}}
            for a, b in compiles]
    out.append({"name": "engine.capture_log", "ph": "i",
                "ts": phases[0][1] * 1e6,
                "args": {"phases": len(phases), "dispatches": len(marks),
                         "dropped": 0, "open": False}})
    return out


def _obs(gaps, phases, marks, compiles=(), shift=0.0, window_s=None,
         trace_t0=None):
    """`gaps` on the LOG's clock; the capture's is `shift` ahead."""
    gap_events = [(label, start + shift, dur) for label, start, dur in gaps]
    sums = {}
    for label, _, dur in gaps:
        sums[label] = sums.get(label, 0.0) + dur
    top = sorted(sums.items(), key=lambda kv: -kv[1])[:10]
    return {"trace": {"devices": 1, "gap_events": gap_events,
                      "window_s": window_s or 6.0, "busy_s": 1.0,
                      "breakdown": {"idle_gaps": [list(kv) for kv in top]}},
            "spans": _events(phases, marks, compiles),
            "trace_t0": trace_t0}


def _simulate(rng, turns=900, launch_jitter=200 * US, stall_every=5):
    """A loop that reads one turn late on a device it mostly keeps fed:
    periodic ticks, a chunk on one turn in seven or so, and a command on
    one turn in five that keeps the host from the device long enough for
    the chip to go idle.  Returns gaps (log clock), phases, marks and
    the truth: seconds of idle by category."""
    t = EPOCH + 0.3
    free = t                      # when the device has run all it holds
    last = "_paged_tick"
    phases, marks, gaps = [], [], []
    truth = dict.fromkeys(CATEGORIES, 0.0)
    ends = []                     # device end of each tick, in order

    def phase(name, dur):
        nonlocal t
        if phases and phases[-1][0] == name:
            phases[-1] = (name, phases[-1][1], t + dur)
        else:
            phases.append((name, t, t + dur))
        t += dur

    def dispatch(program, dur):
        nonlocal free, last
        marks.append((program, t))
        start = max(t + rng.uniform(0, launch_jitter),
                    free + rng.uniform(1, 10) * US)
        if start > free:
            gaps.append((f"after:{last}/before:{program}", free,
                         start - free))
        free, last = start + dur, program
        return start

    next_chunk = 3
    for k in range(turns):
        if k % stall_every == stall_every - 1:
            phase("commands", rng.uniform(6e-3, 9e-3))
        phase("admit", 150 * US)
        if k == next_chunk:
            phase("prefill_dispatch", 250 * US)
            dispatch("_prefill_chunk", rng.uniform(8e-3, 11e-3))
            phase("prefill_dispatch", 60 * US)
            next_chunk += int(rng.integers(4, 11))
        phase("tick_dispatch", 300 * US)
        dispatch("_paged_tick", rng.uniform(5.9e-3, 6.1e-3))
        ends.append(free)
        phase("tick_dispatch", 80 * US)
        if k:                     # read the tick before this one
            wait = max(0.0, ends[-2] + 60 * US - t)
            phase("device_wait", wait + 5 * US)
        phase("emit", rng.uniform(0.8e-3, 1.6e-3))
    # the truth, from the simulator's own books
    for label, start, dur in gaps:
        end = start + dur
        program = label.rpartition("/before:")[2]
        mark = max(m for p, m in marks if p == program and m <= end)
        if mark <= start:
            truth["queued"] += dur
            continue
        truth["launch"] += end - mark
        for name, a, b in phases:
            truth[name] += max(0.0, min(b, mark) - max(a, start))
    return gaps, phases, marks, truth


@pytest.mark.parametrize("clock", ["epoch", "from_the_capture", "boot"])
def test_a_known_offset_is_recovered_on_either_clock(clock):
    rng = np.random.default_rng(61)
    gaps, phases, marks, truth = _simulate(rng)
    shift = {"epoch": 0.137, "from_the_capture": -(EPOCH + 0.21),
             "boot": 4321.5 - EPOCH}[clock]
    obs = _obs(gaps, phases, marks, shift=shift, trace_t0=EPOCH + 0.2)
    value = _metric(obs)
    fit = obs["notes"]["clock_fit"]
    assert abs(fit["offset_s"] - shift) <= 100 * US, fit
    assert fit["votes"] >= 50 and fit["ratio"] >= 3
    assert fit["capture_clock"] == ("epoch" if clock == "epoch"
                                    else "its own")
    assert 0 <= fit["peak_quartiles_us"][0] <= \
        fit["peak_quartiles_us"][2] <= 250
    # four gaps in five closed by a program that was already queued
    queued = sum(1 for label, s, d in gaps if max(
        m for p, m in marks if p == label.rpartition("/before:")[2]
        and m <= s + d) <= s)
    assert queued >= 0.75 * len(gaps)
    got = obs["notes"]["idle_by_phase_s"]
    idle_s = sum(d for _, _, d in gaps)
    assert set(got) == set(CATEGORIES)
    assert sum(got.values()) == pytest.approx(idle_s, rel=1e-4)
    for k in CATEGORIES:
        assert got[k] == pytest.approx(truth[k], abs=0.02 * idle_s), k
    assert got["unseen"] == pytest.approx(0.0, abs=1e-6)
    assert obs["notes"]["idle_unmarked_s"] == 0.0
    assert obs["notes"]["capture_log"]["dropped"] == 0
    host = sum(got[p] for p in ("commands", "sweep", "admit",
                                "prefill_dispatch", "tick_dispatch",
                                "emit", "compile"))
    assert value == pytest.approx(100 * host / 6.0)
    # the labels of breakdown.idle_gaps, each with a split that sums
    by_label = obs["notes"]["idle_gaps_by_phase"]
    assert list(by_label) == [l for l, _ in
                              obs["trace"]["breakdown"]["idle_gaps"]]
    for label, secs in obs["trace"]["breakdown"]["idle_gaps"]:
        assert sum(by_label[label].values()) == \
            pytest.approx(secs, rel=1e-4, abs=1e-6)
    tick = by_label["after:_paged_tick/before:_paged_tick"]
    assert tick["queued"] > 0 and tick["commands"] > 0


def test_a_fed_window_is_fitted_with_the_device_waits_behind_it():
    """Seven late dispatches in 500 turns are no peak of their own; the
    steps' ends, each read a readback later by a `device_wait`, say
    which of the candidates is the offset."""
    rng = np.random.default_rng(3)
    gaps, phases, marks, truth = _simulate(rng, turns=500, stall_every=70,
                                           launch_jitter=80 * US)
    # no chunk's gap to help: the ticks alone
    gaps = [g for g in gaps if g[0].endswith("before:_paged_tick")]
    late = [g for g in gaps if g[2] >= 20 * US]
    assert 4 <= len(late) <= 11
    shift = -(EPOCH + 0.25)
    obs = _obs(gaps, phases, marks, shift=shift)
    assert _metric(obs) is not None
    fit = obs["notes"]["clock_fit"]
    assert fit["votes"] < 12 and "device_wait" in fit["backed_by"]
    assert abs(fit["offset_s"] - shift) <= 100 * US, fit
    got = obs["notes"]["idle_by_phase_s"]
    assert got["commands"] == pytest.approx(
        sum(min(d, 9e-3) for _, _, d in late), rel=0.25)
    # ...and without the waits there is nothing to go by
    bare = _obs(gaps, [p for p in phases if p[0] != "device_wait"], marks,
                shift=shift)
    assert _metric(bare) is None
    assert "offset_s" not in bare["notes"]["clock_fit"]
    # a window with one late dispatch: the waits' edge alone, marked
    # weak, a readback (65 us here) under the offset
    one = [g for g in gaps if g[2] < 20 * US] + late[:1]
    thin = _obs(sorted(one, key=lambda g: g[1]), phases, marks, shift=shift)
    assert _metric(thin) is not None
    fit = thin["notes"]["clock_fit"]
    assert "weak" in fit and fit["wait_votes"] >= 12
    assert -300 * US <= fit["offset_s"] - shift <= 0, fit


def _near(x):
    """Equal to what the fit can resolve: it stands a least launch
    (40 us in `_anchor`) off the truth."""
    return pytest.approx(x, abs=1e-4)


def _anchor(n=80, period=7.3e-3):
    """Enough clean (gap, mark) pairs for a fit: `n` ticks each
    dispatched 40 us before its gap ends, 1 ms into a 1.2 ms gap."""
    gaps, phases, marks = [], [], []
    t = EPOCH + 10.0
    rng = np.random.default_rng(n)
    for i in range(n):
        t += period * rng.uniform(0.7, 1.6)
        gaps.append(("after:_paged_tick/before:_paged_tick", t, 1.2e-3))
        phases.append(("emit", t - 1e-3, t + 0.4e-3))
        phases.append(("tick_dispatch", t + 0.4e-3, t + 1.4e-3))
        phases.append(("device_wait", t + 1.4e-3, t + 3e-3))
        marks.append(("_paged_tick", t + 1.16e-3))
    return gaps, phases, marks


def _case(extra_gaps, extra_phases, extra_marks, compiles=()):
    gaps, phases, marks = _anchor()
    obs = _obs(extra_gaps + gaps, extra_phases + phases,
               extra_marks + marks, compiles, shift=-EPOCH)
    obs["trace"]["gap_events"].sort(key=lambda g: g[1])
    assert _metric(obs) is not None
    label = extra_gaps[0][0]
    return obs["notes"]["idle_gaps_by_phase"][label], obs


def test_a_gap_whose_program_was_marked_before_it_is_queued():
    t = EPOCH + 1.0
    row, _ = _case(
        [("after:_prefill_chunk/before:_paged_block_step", t, 0.25)],
        [("tick_dispatch", t - 0.5, t - 0.4), ("emit", t - 0.4, t + 0.3)],
        [("_paged_block_step", t - 0.45)])
    assert row == {"queued": _near(0.25)}
    # ...but for what a compile covers of it: the program was marked,
    # then compiled inside the call that was to hand it over
    row, _ = _case(
        [("after:_paged_tick/before:paged_read_pages", t, 0.25)],
        [("sweep", t - 0.1, t + 0.3)],
        [("paged_read_pages", t - 0.05)], compiles=[(t - 0.04, t + 0.21)])
    assert row == {"compile": _near(0.21), "queued": _near(0.04)}


def test_a_gap_is_split_before_and_after_its_mark():
    t = EPOCH + 1.0
    row, obs = _case(
        [("after:_paged_tick/before:paged_read_pages", t, 0.5)],
        [("idle", t - 0.2, t + 0.3), ("sweep", t + 0.3, t + 0.6)],
        [("paged_read_pages", t + 0.45)])
    assert row == {"idle": _near(0.3), "sweep": _near(0.15),
                   "launch": _near(0.05)}
    # `idle` is the traffic's, `launch` the runtime's: neither is the
    # host's work
    ticks = obs["notes"]["idle_gaps_by_phase"][
        "after:_paged_tick/before:_paged_tick"]
    host_s = 0.15 + ticks.get("emit", 0) + ticks.get("tick_dispatch", 0)
    assert _metric(obs) == pytest.approx(100 * host_s / 6.0, rel=1e-3)


def test_a_compile_takes_precedence_over_the_phase_it_falls_in():
    t = EPOCH + 1.0
    row, _ = _case(
        [("after:_paged_tick/before:paged_read_pages", t, 0.5)],
        [("sweep", t - 0.1, t + 0.6)],
        [("paged_read_pages", t + 0.48)],
        compiles=[(t + 0.05, t + 0.40), (t + 0.47, t + 0.49)])
    # (the second compile runs through the mark: a program compiles
    # inside the call that hands it over, and that is no launch)
    assert row == {"compile": _near(0.37),
                   "sweep": _near(0.12),
                   "launch": _near(0.01)}


def test_an_unmarked_program_is_split_by_overlap_and_summed_apart():
    t = EPOCH + 1.0
    row, obs = _case(
        [("after:copy/before:convert_element_type", t, 0.1),
         ("after:x/before:y", EPOCH + 30.0, 0.05)],
        [("emit", t - 0.1, t + 0.04), ("admit", t + 0.04, t + 0.2)], [])
    assert row == {"emit": _near(0.04),
                   "admit": _near(0.06)}
    # idle outside the log is unseen; unmarked is a sum apart
    assert obs["notes"]["idle_gaps_by_phase"]["after:x/before:y"] == \
        {"unseen": _near(0.05)}
    assert obs["notes"]["idle_unmarked_s"] == _near(0.15)
    got = obs["notes"]["idle_by_phase_s"]
    assert sum(got.values()) == pytest.approx(
        sum(g[2] for g in obs["trace"]["gap_events"]), abs=1e-5)


def test_the_offset_is_the_least_launch_of_any_program():
    """A step of many leaves takes 0.6 ms longer to hand over than an
    eager scalar: the offset is the scalar's edge, so the scalar's mark
    is not read as later than its start (and its gap as `queued`)."""
    rng = np.random.default_rng(7)
    gaps, phases, marks = [], [], []
    t = EPOCH + 10.0
    for i in range(120):
        t += rng.uniform(5e-3, 12e-3)
        program, launch = (("_paged_tick", 640 * US) if i % 3 else
                           ("convert_element_type", 40 * US))
        launch += rng.uniform(0, 50 * US)
        gaps.append((f"after:copy/before:{program}", t, 1.5e-3))
        phases.append(("emit", t - 1e-3, t + 0.4e-3))
        phases.append(("tick_dispatch", t + 0.4e-3, t + 2e-3))
        marks.append((program, t + 1.5e-3 - launch))
    obs = _obs(gaps, phases, marks, shift=-EPOCH)
    assert _metric(obs) is not None
    fit = obs["notes"]["clock_fit"]
    assert fit["offset_s"] == pytest.approx(-EPOCH + 40 * US, abs=60 * US)
    over = fit["least_launch_over_the_offset_us"]
    assert over["convert_element_type"] == 0.0
    assert over["_paged_tick"] == pytest.approx(600, abs=60)
    rows = obs["notes"]["idle_gaps_by_phase"]
    scalar = rows["after:copy/before:convert_element_type"]
    assert "queued" not in scalar
    assert scalar["launch"] == pytest.approx(40 * 25 * US, abs=40 * 50 * US)
    assert rows["after:copy/before:_paged_tick"]["launch"] == \
        pytest.approx(80 * 665 * US, rel=0.1)


def _poor_fit():
    # every program queued long before its gap: the pairs agree on
    # nothing
    rng = np.random.default_rng(5)
    gaps, phases, marks = [], [], []
    t = EPOCH
    for _ in range(300):
        t += rng.uniform(4e-3, 9e-3)
        gaps.append(("after:_paged_tick/before:_paged_tick", t, 2e-4))
        marks.append(("_paged_tick", t - rng.uniform(3e-3, 30e-3)))
        phases.append(("emit", t - 1e-3, t + 1e-3))
    return _obs(gaps, phases, sorted(marks, key=lambda m: m[1]))


def _parents():
    gaps, phases, marks = _anchor()
    obs = _obs(gaps, phases, marks)
    obs["spans"] = obs["spans"][:2]          # the ring alone: no log
    return obs


def _empty_trace():
    gaps, phases, marks = _anchor()
    obs = _obs(gaps, phases, marks)
    obs["trace"] = {"devices": 0}
    return obs


@pytest.mark.parametrize("make,noted", [
    (_poor_fit, True), (_parents, False), (_empty_trace, False),
    (lambda: {"trace": None, "spans": None}, False)],
    ids=["poor_fit", "a_parents_obs", "empty_trace", "untraced"])
def test_nothing_to_join_reads_none(make, noted):
    obs = make()
    assert _metric(obs) is None
    notes = obs.get("notes") or {}
    assert ("clock_fit" in notes) == noted
    assert "idle_by_phase_s" not in notes
    if noted:
        assert "offset_s" not in notes["clock_fit"]


def test_the_two_entries_are_parts_of_device_idle_share():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    by_name = {m["name"]: m for m in spec["per_layer"]}
    for tag, moves in (("tput", "out_tok_per_s"), ("chat", "itl_p90_ms")):
        entry = by_name[f"idle_host_work_share.{tag}"]
        whole = by_name[f"device_idle_share.{tag}"]
        assert entry == {**whole, "name": entry["name"],
                         "source": "program_span"}
        assert entry["moves"] == moves and entry["better"] == "lower"
    # appended to the 94 entries that were there, in this order (later
    # PRs append after them)
    assert [m["name"] for m in spec["per_layer"][94:96]] == \
        ["idle_host_work_share.tput", "idle_host_work_share.chat"]
    with open(os.path.join(ROOT, "benchmarks", "metrics",
                           "idle_host_work_share.json")) as f:
        assert json.load(f) == {"reader": "idle_by_phase", "args": {
            "phases": ["commands", "sweep", "admit", "prefill_dispatch",
                       "tick_dispatch", "emit", "compile"]}}
