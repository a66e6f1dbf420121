"""The engine loop accounts for its own time (PR 25).

`GenerationEngine.stats()` carries cumulative per-phase seconds of the
worker loop (`loop_s_*`, a partition of the thread's wall time), turn
and token-gap counts, sweep counts and the process's compile counters;
a sweep that moved pages leaves one `engine.tier_sweep` span; the
compile listener (`jax_utils.install_compile_listener`) counts compiles
where they happen; `benchmarks/readers/stats_delta.py` turns two
`stats()` snapshots into a per-layer metric.
"""

import importlib.util
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu._private import jax_utils
from ray_tpu._private import tracing
from ray_tpu.models import gpt
from ray_tpu.serve.llm.engine import LOOP_PHASES, GenerationEngine

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


def _loop_s(stats):
    return sum(getattr(stats, f"loop_s_{p}") for p in LOOP_PHASES)


def _engine_config():
    """The config object the ENGINE reads (bound when its module was
    imported): `config.GLOBAL_CONFIG` is rebound by every
    `rt.init(_system_config=...)`, so a test that imports the name after
    one in its worker would patch an object the engine never looks at."""
    from ray_tpu.serve.llm import engine as engine_mod
    return engine_mod._cfg


def _engine_spans(name):
    return [e for e in tracing.ring().snapshot() if e.get("name") == name]


def _settle(eng):
    """One more trip through the loop: the turn that emitted the last
    token has closed its books when this returns."""
    eng.run_on_worker(lambda: None)
    eng.run_on_worker(lambda: None)


def _sweep(eng):
    return eng.run_on_worker(lambda: eng._maybe_sweep_tiers(force=True))


def test_stats_names_every_phase():
    with _engine("acct-fields") as eng:
        d = eng.stats().to_dict()
    assert [k for k in d if k.startswith("loop_s_")] == \
        [f"loop_s_{p}" for p in LOOP_PHASES]
    for k in ("loop_turns", "loop_turns_with_chunk", "loop_turns_ahead",
              "token_gaps",
              "token_gaps_stalled", "kv_sweeps", "kv_sweep_s",
              "jit_compiles", "jit_compile_s"):
        assert k in d


def test_phases_partition_the_loop_threads_wall_time():
    """Over a run of a few hundred turns, busy stretches and idle waits
    both, the eight loop_s_* sum to the thread's wall time."""
    with _engine("acct-partition", kv_tiering=False) as eng:
        eng.submit(_prompt(1, 6), max_new_tokens=4).result(timeout=120)
        s0, t0 = eng.stats(), time.monotonic()
        for i in range(8):
            streams = [eng.submit(_prompt(10 * i + j, 7 + j),
                                  max_new_tokens=30) for j in range(3)]
            for st in streams:
                assert len(st.result(timeout=120)) == 30
            time.sleep(0.05)     # an idle stretch between the bursts
        s1, t1 = eng.stats(), time.monotonic()
    turns = s1.loop_turns - s0.loop_turns
    assert turns >= 200, turns
    wall = t1 - t0
    assert abs((_loop_s(s1) - _loop_s(s0)) - wall) <= 0.05 * wall
    assert abs((s1.uptime_s - s0.uptime_s) - wall) <= 0.05 * wall
    # every phase the run went through took some time, and no other did
    for p in ("idle", "admit", "prefill_dispatch", "tick_dispatch",
              "device_wait", "emit"):
        assert getattr(s1, f"loop_s_{p}") > getattr(s0, f"loop_s_{p}"), p
    assert s1.loop_s_sweep == s0.loop_s_sweep == 0.0
    assert s1.loop_s_commands == s0.loop_s_commands


def test_phases_partition_the_time_between_two_reads_mid_stream():
    """Both snapshots are taken while rows decode and a tick is in
    flight (the loop reads a tick one turn late): the eight loop_s_*
    still sum to the time between them, `device_wait` is what the
    thread waited, and most turns dispatched ahead of their read."""
    import threading
    done = threading.Event()

    def load(eng):
        i = 0
        while not done.is_set():
            streams = [eng.submit(_prompt(900 + 3 * i + j, 6 + j),
                                  max_new_tokens=38) for j in range(3)]
            for st in streams:
                st.result(timeout=120)
            i += 1

    with _engine("acct-ahead", kv_tiering=False) as eng:
        eng.submit(_prompt(1, 6), max_new_tokens=4).result(timeout=120)
        th = threading.Thread(target=load, args=(eng,), daemon=True)
        th.start()
        try:
            _wait(lambda: eng.stats().loop_turns_ahead > 20)
            s0, t0 = eng.stats(), time.monotonic()
            _wait(lambda: eng.stats().loop_turns - s0.loop_turns > 300)
            s1, t1 = eng.stats(), time.monotonic()
        finally:
            done.set()
            th.join(120)
    wall = t1 - t0
    assert abs((_loop_s(s1) - _loop_s(s0)) - wall) <= 0.05 * wall
    assert abs((s1.uptime_s - s0.uptime_s) - wall) <= 0.05 * wall
    turns = s1.loop_turns - s0.loop_turns
    ahead = s1.loop_turns_ahead - s0.loop_turns_ahead
    assert 0.7 * turns < ahead <= turns
    for p in ("tick_dispatch", "device_wait", "emit"):
        assert getattr(s1, f"loop_s_{p}") > getattr(s0, f"loop_s_{p}"), p
    assert s1.token_gaps_stalled == s0.token_gaps_stalled


def test_turns_with_chunk_equal_chunks_dispatched():
    with _engine("acct-chunks", kv_tiering=False,
                 enable_prefix_cache=False) as eng:
        lens = [3, 5, 6, 11, 23]
        for i, n in enumerate(lens):
            eng.submit(_prompt(100 + i, n),
                       max_new_tokens=3).result(timeout=120)
        _settle(eng)
        s = eng.stats()
    chunk = ENGINE_KW["prefill_chunk"]
    assert s.loop_turns_with_chunk == sum(-(-n // chunk) for n in lens)
    assert s.loop_turns_with_chunk <= s.loop_turns


def test_token_gaps_are_tokens_out_less_first_tokens():
    with _engine("acct-gaps", kv_tiering=False) as eng:
        outs = [eng.submit(_prompt(200 + i, 5 + i), max_new_tokens=n)
                for i, n in enumerate((1, 2, 9, 17))]
        for st in outs:
            st.result(timeout=120)
        _settle(eng)
        s = eng.stats()
    assert s.tokens_generated == 1 + 2 + 9 + 17
    assert s.token_gaps == s.tokens_generated - 4
    # no tiering, no command during a decoding turn, no compile after
    # the warm-up: nothing stalled a gap
    assert s.kv_sweeps == 0 and s.kv_sweep_s == 0.0


def test_stalled_gaps_zero_without_tiering():
    with _engine("acct-nostall", kv_tiering=False) as eng:
        eng.submit(_prompt(300, 9), max_new_tokens=8).result(timeout=120)
        _settle(eng)
        warm = eng.stats()
        for i in range(3):
            eng.submit(_prompt(301 + i, 9),
                       max_new_tokens=12).result(timeout=120)
        _settle(eng)
        s = eng.stats()
    assert s.token_gaps - warm.token_gaps == 3 * 11
    assert s.token_gaps_stalled == warm.token_gaps_stalled


def _wait(cond, timeout=60.0):
    t0 = time.monotonic()
    while not cond():
        assert time.monotonic() - t0 < timeout
        time.sleep(0.002)


def test_stalled_gaps_rise_only_in_the_turn_of_a_sweep_that_moved_pages(
        monkeypatch):
    cfg = _engine_config()
    monkeypatch.setattr(cfg, "serve_kv_demote_idle_s", 0.0)
    monkeypatch.setattr(cfg, "serve_kv_tier_sweep_s", 3600.0)
    with _engine("acct-stall", kv_tiering=True, max_seq=400,
                 kv_pages=240) as eng:
        # leave tree-only pages behind for the sweep to find
        eng.submit(_prompt(400, 16), max_new_tokens=4).result(timeout=120)
        _settle(eng)
        base = eng.stats()
        assert base.kv_sweeps == 0 and base.token_gaps_stalled == 0

        rows = [eng.submit(_prompt(401 + i, 6), max_new_tokens=380)
                for i in range(2)]
        for g in [iter(st) for st in rows]:
            next(g), next(g)          # both rows are decoding
        # The loop's OWN sweep (no command involved) falls due in the
        # next turn; it moves the cold pages, and the gaps of that one
        # turn — one per decoding row — are the stalled ones.
        eng._last_sweep = float("-inf")
        _wait(lambda: eng.stats().kv_sweeps == 1)
        _wait(lambda: eng.stats().token_gaps_stalled > 0)
        # (the pages' host half runs on the lander; the loop commits
        # them at the top of a later turn)
        _wait(lambda: eng.stats().kv_demotions > base.kv_demotions)
        s = eng.stats()
        assert s.loop_s_sweep >= s.kv_sweep_s > 0
        assert s.token_gaps_stalled == 2

        # A sweep that finds nothing to move stalls nothing and counts
        # nothing: the rows' own pages are held, the rest already went.
        eng._last_sweep = float("-inf")
        _wait(lambda: eng._last_sweep > 0)
        gaps = eng.stats().token_gaps
        _wait(lambda: eng.stats().token_gaps > gaps + 4)
        s2 = eng.stats()
        assert s2.kv_sweeps == 1 and s2.kv_sweep_s == s.kv_sweep_s
        assert s2.token_gaps_stalled == 2
        assert s2.loop_s_sweep > s.loop_s_sweep   # the scan still took time
        for st in rows:
            st.cancel()


def test_tier_sweep_span_matches_the_counters(monkeypatch):
    cfg = _engine_config()
    monkeypatch.setattr(cfg, "serve_kv_demote_idle_s", 0.0)
    monkeypatch.setattr(cfg, "serve_kv_tier_sweep_s", 3600.0)
    with _engine("acct-span", kv_tiering=True) as eng:
        eng.submit(_prompt(500, 20), max_new_tokens=4).result(timeout=120)
        _settle(eng)
        n0 = len(_engine_spans("engine.tier_sweep"))
        d0 = eng.stats().kv_demotions
        moved = _sweep(eng)
        s = eng.stats()
        assert _sweep(eng) == 0          # nothing left: no second span
    spans = _engine_spans("engine.tier_sweep")[n0:]
    assert len(spans) == 1
    a = spans[0]["args"]
    assert a["pages"] == moved == s.kv_demotions - d0 > 0
    assert a["to_t1"] + a["to_t2"] == a["pages"]
    assert a["cause"] == "idle"
    dur_ms = spans[0]["dur"] / 1e3
    parts = a["read_ms"] + a["frame_ms"] + a["put_ms"]
    assert 0 < parts <= dur_ms + 0.01
    assert 0 <= a["compile_ms"] <= a["read_ms"] + 0.01
    assert abs(dur_ms - s.kv_sweep_s * 1e3) < 0.01


def test_ring_gains_no_event_per_turn():
    """With no profiler session the ring holds the three spans of each
    request and nothing per turn, however many turns ran."""
    with _engine("acct-ring", kv_tiering=False) as eng:
        eng.submit(_prompt(600, 5), max_new_tokens=2).result(timeout=120)
        _settle(eng)
        n0 = sum(1 for e in tracing.ring().snapshot()
                 if str(e.get("name", "")).startswith("engine."))
        for i in range(3):
            eng.submit(_prompt(601 + i, 9),
                       max_new_tokens=38).result(timeout=120)
        _settle(eng)
        assert eng.stats().loop_turns > 100
    n1 = sum(1 for e in tracing.ring().snapshot()
             if str(e.get("name", "")).startswith("engine."))
    assert n1 - n0 == 3 * 3


# ---------------------------------------------------------------------------
# The compile listener


def test_compile_listener_counts_a_fresh_jit_once():
    jax_utils.install_compile_listener()
    x = jnp.arange(7.0)
    jax.block_until_ready(x)

    @jax.jit
    def fresh(v):
        return (v * 3.0 + 1.0).sum()

    n0, s0 = jax_utils.compile_counters()
    ring0 = len(_engine_spans("jax.compile"))
    t0 = time.time()
    fresh(x).block_until_ready()
    wall = time.time() - t0
    n1, s1 = jax_utils.compile_counters()
    assert n1 - n0 == 1
    # seconds are time on the clock (nested stages are not summed twice)
    assert 0 < s1 - s0 <= wall + 1e-3
    events = _engine_spans("jax.compile")[ring0:]
    assert len(events) == 1
    assert "fresh" in events[0]["args"]["fun_name"]
    assert events[0]["args"]["from_cache"] is False
    assert events[0]["dur"] > 0

    fresh(x).block_until_ready()           # the second call compiles nothing
    assert jax_utils.compile_counters() == (n1, s1)


def test_compile_listener_installed_twice_counts_once():
    jax_utils.install_compile_listener()
    jax_utils.install_compile_listener()
    from jax._src import monitoring
    assert monitoring.get_event_time_span_listeners().count(
        jax_utils._on_compile_stage) == 1
    assert monitoring.get_event_duration_listeners().count(
        jax_utils._on_cache_load) == 1
    x = jnp.arange(5.0)
    jax.block_until_ready(x)
    n0, _ = jax_utils.compile_counters()
    jax.jit(lambda v: v - 2.5)(x).block_until_ready()
    assert jax_utils.compile_counters()[0] - n0 == 1


def test_engine_surfaces_the_process_compile_counters():
    with _engine("acct-jit", kv_tiering=False) as eng:
        eng.submit(_prompt(700, 5), max_new_tokens=2).result(timeout=120)
        x = jnp.arange(11.0)
        jax.block_until_ready(x)
        s = eng.stats()
        assert (s.jit_compiles, round(s.jit_compile_s, 3)) == (
            jax_utils.compile_counters()[0],
            round(jax_utils.compile_counters()[1], 3))
        x = jnp.arange(11.0)
        jax.block_until_ready(x)
        jax.jit(lambda v: v * 0.125)(x).block_until_ready()
        assert eng.stats().jit_compiles == s.jit_compiles + 1


# ---------------------------------------------------------------------------
# benchmarks/readers/stats_delta.py on hand-made snapshots


def _benchmark_module(*parts):
    """A file of benchmarks/, loaded by path as the harness loads it."""
    spec = importlib.util.spec_from_file_location(
        parts[-1][:-3], os.path.join(ROOT, "benchmarks", *parts))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _stats_delta():
    return _benchmark_module("readers", "stats_delta.py").read


OBS = {"stats0": {"a": 1.0, "b": 10, "c": 100, "turns": 5},
       "stats1": {"a": 2.5, "b": 14, "c": 100, "turns": 30}}


@pytest.mark.parametrize("num,den,scale,want", [
    (["a"], [], 1, 1.5),                       # a plain difference
    (["a"], [], 1000, 1500.0),                 # seconds -> ms
    (["a", "b"], ["turns"], 1000, 220.0),      # a sum per turn
    (["b"], ["a", "b"], 100, 100 * 4 / 5.5),   # a share
    (["c"], ["turns"], 1, 0.0),                # nothing moved: 0, not None
])
def test_stats_delta_reads(num, den, scale, want):
    assert _stats_delta()(OBS, num=num, den=den, scale=scale) == \
        pytest.approx(want)


@pytest.mark.parametrize("obs,num,den", [
    (OBS, ["missing"], []),                    # a key the program lacks
    (OBS, ["a"], ["missing"]),
    (OBS, ["a"], ["c"]),                       # denominator did not move
    ({"stats0": OBS["stats0"]}, ["a"], []),    # no closing snapshot
    ({}, ["a"], []),
])
def test_stats_delta_finds_nothing_to_read(obs, num, den):
    assert _stats_delta()(obs, num=num, den=den, scale=1) is None


def _metric(name, obs):
    """metrics/<name>.json read as the harness reads it."""
    with open(os.path.join(ROOT, "benchmarks", "metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "stats_delta"
    return _stats_delta()(obs, **spec["args"])


# three prompts of 700 tokens in chunks of 256: 9 chunks, 204 pads
CHUNKED = {"stats0": {"prefill_tokens": 10, "prefill_pad_tokens": 22,
                      "loop_turns_with_chunk": 1},
           "stats1": {"prefill_tokens": 2110, "prefill_pad_tokens": 226,
                      "loop_turns_with_chunk": 10}}


@pytest.mark.parametrize("program,per_chunk,pad_share", [
    ("with_the_counter", 2100 / 9, 100 * 204 / 2304),
    ("before_it", 2100 / 9, None),     # a parent commit: nothing, no raise
])
def test_prefill_width_metrics_read_the_engines_counters(
        program, per_chunk, pad_share):
    obs = CHUNKED if program == "with_the_counter" else {
        end: {k: v for k, v in st.items() if k != "prefill_pad_tokens"}
        for end, st in CHUNKED.items()}
    assert _metric("prefill_tokens_per_chunk", obs) == \
        pytest.approx(per_chunk)
    assert _metric("prefill_pad_share", obs) == (
        None if pad_share is None else pytest.approx(pad_share))


@pytest.mark.parametrize("program,want", [
    ("with_the_counter", 100 * 36 / 40),
    ("before_it", None),               # a parent commit: nothing, no raise
])
def test_tick_ahead_share_reads_the_engines_counter(program, want):
    obs = {"stats0": {"loop_turns": 10, "loop_turns_ahead": 4},
           "stats1": {"loop_turns": 50, "loop_turns_ahead": 40}}
    if program == "before_it":
        obs = {end: {"loop_turns": st["loop_turns"]}
               for end, st in obs.items()}
    assert _metric("tick_ahead_share", obs) == (
        None if want is None else pytest.approx(want))


def test_tick_ahead_share_is_the_throughput_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry = next(m for m in spec["per_layer"]
                 if m["name"] == "tick_ahead_share.tput")
    assert entry == {
        "name": "tick_ahead_share.tput", "unit": "%", "better": "higher",
        "source": "program_counter",
        "layer": "Scheduler (serve/llm/scheduler.py, engine.py admission)",
        "moves": "out_tok_per_s",
        "workloads": ["internlm2-batch", "sala-longdoc", "dsv2-decode",
                      "kexaone-reason", "jamba2-chat", "mimo-agent",
                      "zaya-reason", "sdar-blockgen", "ling3-longtail",
                      "glm5-longctx"]}
    tput = next(m for m in spec["end_to_end"]
                if m["name"] == "out_tok_per_s")
    assert entry["workloads"] == tput["workloads"]


# ---------------------------------------------------------------------------
# benchmarks/tools/host_gaps.py on hand-made intervals


def _host_gaps():
    return _benchmark_module("tools", "host_gaps.py")


def test_host_gaps_splits_idle_by_host_phase():
    hg = _host_gaps()
    # device: tick ends at 1.000, next program starts at 1.400 (a sweep
    # then its read), then 0.010 idle twice around a tick
    gaps = [("after:_paged_tick/before:paged_read_pages", 1.000, 0.400),
            ("after:_paged_tick/before:_paged_tick", 2.000, 0.010),
            ("after:_paged_tick/before:_paged_tick", 3.000, 0.010)]
    phases = [("engine.emit", 1.000, 0.020), ("engine.sweep", 1.020, 0.500),
              ("engine.emit", 2.001, 0.004),
              ("engine.tick_dispatch", 2.005, 0.004),
              ("engine.tick_dispatch", 3.006, 0.010)]
    out = hg.split_gaps(gaps, phases)
    sweep = out["after:_paged_tick/before:paged_read_pages"]
    assert sweep["total"] == pytest.approx(0.400)
    assert sweep["sweep"] == pytest.approx(0.380)
    assert sweep["emit"] == pytest.approx(0.020)
    assert sweep[hg.NO_PHASE] == pytest.approx(0.0, abs=1e-9)
    tick = out["after:_paged_tick/before:_paged_tick"]
    assert tick["total"] == pytest.approx(0.020)
    assert tick["emit"] == pytest.approx(0.004)
    assert tick["tick_dispatch"] == pytest.approx(0.004 + 0.004)
    assert tick[hg.NO_PHASE] == pytest.approx(0.020 - 0.012)
    # idle after the last host phase the capture kept (3.016) is named
    # as the capture's edge, not as time under no phase
    late = hg.split_gaps([("after:x/before:y", 3.010, 0.100)], phases)
    assert late["after:x/before:y"][hg.EDGE] == pytest.approx(0.094)
    assert late["after:x/before:y"]["tick_dispatch"] == \
        pytest.approx(0.006)
    assert late["after:x/before:y"][hg.NO_PHASE] == \
        pytest.approx(0.0, abs=1e-9)


def test_host_gaps_states_the_clock_offset():
    hg = _host_gaps()
    ms = 1e6
    modules = [["jit__paged_tick(7)", i * 100 * ms, 60 * ms]
               for i in range(8)] + [["jit__prefill_chunk(9)", 65 * ms,
                                      30 * ms]]
    # each wait returns 0.2 ms after its tick ends; one straggler 3 ms
    phases = [("engine.device_wait", i * 0.1 + 0.010,
               0.050 + (0.003 if i == 5 else 0.0002)) for i in range(8)]
    phases.append(("engine.emit", 0.0605, 0.001))
    off = hg.clock_offset(modules, phases)
    assert off["ticks"] == 8 and off["waits"] == 8
    assert off["median_ms"] == pytest.approx(0.2, abs=1e-6)
    assert off["max_ms"] == pytest.approx(3.0, abs=1e-6)
    assert off["within_5ms_share"] == 1.0
    assert hg.clock_offset(modules, [])["waits"] == 0
