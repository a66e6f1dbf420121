"""Device-trace capture (util/tpu_profiler.py) — works on the CPU
backend too; the artifact contract is a TensorBoard/Perfetto-loadable
trace directory."""

import glob
import os

import jax.numpy as jnp
import pytest

from ray_tpu.util import tpu_profiler


def test_trace_context_produces_artifacts(tmp_path):
    d = str(tmp_path / "prof")
    with tpu_profiler.trace(d) as got:
        assert got == d
        with tpu_profiler.annotate("matmul-region"):
            x = jnp.ones((64, 64))
            (x @ x).block_until_ready()
    files = glob.glob(os.path.join(d, "**", "*"), recursive=True)
    assert any(f.endswith(".trace.json.gz") or ".xplane." in f
               for f in files), files


def test_start_stop_guards(tmp_path):
    with pytest.raises(RuntimeError):
        tpu_profiler.stop()
    d = tpu_profiler.start(str(tmp_path / "p2"))
    with pytest.raises(RuntimeError):
        tpu_profiler.start(str(tmp_path / "p3"))
    out = tpu_profiler.stop()
    assert out == d


def _host_events(trace_dir):
    """{event name: count} over the host planes of a capture."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert paths, trace_dir
    names = {}
    for plane in ProfileData.from_file(sorted(paths)[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                names[e.name] = names.get(e.name, 0) + 1
    return names


def test_start_keeps_annotations_with_the_python_tracer_off(tmp_path):
    """start() captures what the benchmark's probe captures: annotated
    regions and XLA's host events, no event per Python call."""
    d = tpu_profiler.start(str(tmp_path / "p4"))
    with tpu_profiler.annotate("kept-region"):
        (jnp.ones((8, 8)) + 1).block_until_ready()
    tpu_profiler.stop()
    names = _host_events(d)
    assert names.get("kept-region") == 1
    assert not any(n.startswith("$") for n in names), \
        [n for n in names if n.startswith("$")][:5]


def test_capture_of_a_running_engine_holds_its_phases(tmp_path):
    """The engine loop's phases land in the capture's host plane, on the
    profiler's clock: one engine.tick_dispatch and one
    engine.device_wait per decode tick."""
    import jax

    from ray_tpu.models import gpt
    from ray_tpu.serve.llm.engine import GenerationEngine

    cfg = gpt.GPTConfig(vocab_size=97, d_model=32, n_heads=4, n_layers=2,
                        d_ff=64, max_seq=64, dtype=jnp.float32,
                        remat=False, use_flash=False)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    with GenerationEngine(params, cfg, name="profiled", num_slots=2,
                          max_seq=48, prefill_chunk=5, page_size=4,
                          kv_pages=40, kv_tiering=False) as eng:
        eng.submit([3, 4, 5], max_new_tokens=2).result(timeout=120)
        d = tpu_profiler.start(str(tmp_path / "p5"))
        turns0 = eng.stats().loop_turns
        out = eng.submit([5, 6, 7, 8, 9, 10, 11], max_new_tokens=12)
        assert len(out.result(timeout=120)) == 12
        eng.run_on_worker(lambda: None)
        turns = eng.stats().loop_turns - turns0
        tpu_profiler.stop()
    names = _host_events(d)
    # 11 ticks after the first token; the prompt's 2 chunks are ONE
    # region, two turns in the same phase with nothing between them
    assert names.get("engine.tick_dispatch") == 11
    assert names.get("engine.prefill_dispatch") == 1
    assert names.get("engine.device_wait") == 12   # 11 ticks + last chunk
    assert names.get("engine.emit", 0) >= 11
    assert names.get("engine.commands") == 1
    assert "engine.idle" not in names
    assert turns >= 13
