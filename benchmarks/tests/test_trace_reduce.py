"""The trace reducer: interval arithmetic on hand-made device lines, and
(below) the same numbers read from a small trace recorded on the chip."""

import os

import pytest

from benchmarks.lib import trace_reduce as tr

MS = 1e6   # ns


def _plane(ops, modules, name="/device:TPU:0"):
    return {"name": name, "lines": [{"name": "XLA Ops", "events": ops},
                                    {"name": "XLA Modules",
                                     "events": modules}]}


def test_interval_arithmetic():
    assert tr.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert tr.total(tr.union([(0, 2), (1, 3), (5, 6)])) == 4
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert tr.subtract([(0, 4)], []) == [(0, 4)]
    assert tr.program_name("jit__paged_tick(1234)") == "jit__paged_tick"


def test_busy_idle_programs_and_gaps():
    # tick 0-10 ms, idle 10-14, chunk 14-20, idle 20-21, tick 21-31,
    # read_pages 31-32, idle 32-40 (the sweep's host work), tick 40-50
    modules = [["jit__paged_tick(1)", 0, 10 * MS],
               ["jit__prefill_chunk(2)", 14 * MS, 6 * MS],
               ["jit__paged_tick(1)", 21 * MS, 10 * MS],
               ["jit_paged_read_pages(3)", 31 * MS, 1 * MS],
               ["jit__paged_tick(1)", 40 * MS, 10 * MS]]
    ops = [["while.3", 0, 10 * MS], ["fusion.1", 1 * MS, 2 * MS],
           ["copy-start.5", 0, 10 * MS],          # async, spans the while
           ["while.4", 14 * MS, 6 * MS],
           ["while.3", 21 * MS, 10 * MS], ["fusion.1", 22 * MS, 2 * MS],
           ["gather.9", 31 * MS, 1 * MS],
           ["while.3", 40 * MS, 10 * MS]]
    out = tr.reduce({"planes": [_plane(ops, modules),
                                {"name": "/host:CPU", "lines": []}]})
    assert out["devices"] == 1
    assert out["busy_s"] == pytest.approx(0.037)
    assert out["window_s"] == pytest.approx(0.050)
    assert out["programs"]["jit__paged_tick"] == [0.010, 0.010, 0.010]
    assert out["programs"]["jit__prefill_chunk"] == [0.006]
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert gaps["after:paged_read_pages/before:_paged_tick"] == \
        pytest.approx(0.008)
    assert gaps["after:_paged_tick/before:_prefill_chunk"] == \
        pytest.approx(0.004)
    assert gaps["after:_prefill_chunk/before:_paged_tick"] == \
        pytest.approx(0.001)
    top = dict(out["breakdown"]["device_ops"])
    assert top["jit__paged_tick/while.3"] == pytest.approx(0.030)
    assert top["jit__paged_tick/fusion.1"] == pytest.approx(0.004)
    # the caller's own clock for the window wins over the trace's span
    assert tr.reduce({"planes": [_plane(ops, modules)]},
                     window_s=0.1)["window_s"] == 0.1
    # the reader of tier_stall_ms: the program's own time + what follows
    from benchmarks.lib.registry import Registry, DEFAULT_ROOT
    stall = Registry(DEFAULT_ROOT).reader("trace_stall")
    assert stall({"trace": out}, "paged_read_pages") == pytest.approx(9.0)
    idle = Registry(DEFAULT_ROOT).reader("device_idle")
    assert idle({"trace": out}) == pytest.approx(26.0)
    assert idle({"trace": None}) is None


def test_exposed_collectives_and_chip_average():
    # device 0: all-gather 0-4 ms with compute 2-6; all-reduce 8-10 alone
    ops0 = [["while.1", 0, 10 * MS],
            ["all-gather-start.1", 0, 4 * MS], ["fusion.7", 2 * MS, 4 * MS],
            ["all-reduce.2", 8 * MS, 2 * MS]]
    # device 1: busy throughout, no collective exposed
    ops1 = [["fusion.7", 0, 10 * MS], ["all-reduce.2", 3 * MS, 2 * MS]]
    mods = [["jit_train_step(9)", 0, 10 * MS]]
    out = tr.reduce({"planes": [_plane(ops0, mods, "/device:TPU:0"),
                                _plane(ops1, mods, "/device:TPU:1")]})
    assert out["devices"] == 2
    assert out["collective_s"] == pytest.approx((0.006 + 0.002) / 2)
    assert out["collective_exposed_s"] == pytest.approx((0.004 + 0.0) / 2)
    assert out["busy_s"] == pytest.approx(0.010)
    assert tr.reduce({"planes": []}) == {"devices": 0}


def test_recorded_chip_trace():
    """0.7 s of `mistral7b-chat` on one v5e chip (PR 24's first chip run;
    the device plane's three lines, HLO texts cut to their names): seven
    decode ticks interleaved with four prefill chunks."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "chat_slice.xplane.pb")
    out = tr.reduce(tr.load(path))
    assert out["devices"] == 1
    ticks = out["programs"]["jit__paged_tick"]
    chunks = out["programs"]["jit__prefill_chunk"]
    assert len(ticks) == 7 and len(chunks) == 4
    assert all(0.0605 < t < 0.0607 for t in ticks)
    assert all(0.0460 < t < 0.0462 for t in chunks)
    assert out["busy_s"] == pytest.approx(0.663565465)
    assert out["window_s"] == pytest.approx(0.699789455)
    assert 0 < out["busy_s"] / out["window_s"] <= 1
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["jit__paged_tick/while.3"] == pytest.approx(0.343099232)
    assert ops["jit__prefill_chunk/while.4"] == pytest.approx(0.138059938)
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert gaps["after:_paged_tick/before:convert_element_type"] == \
        pytest.approx(0.01316298)
    # (the slice ends inside a tick whose module event was cut away, so
    # programs + gaps fall short of the window by that tick's part)
    assert sum(gaps.values()) == pytest.approx(0.032203432)
    assert out["busy_s"] + sum(gaps.values()) <= out["window_s"]
    assert out["collective_s"] == 0 and out["collective_exposed_s"] == 0
    assert tr.op_name("%while.3 = (s32[]{:T(128)}) while(...)") == "while.3"
    # names as the four-chip train trace has them (PR 24)
    assert tr.op_name(
        "%psum.125 = f32[4,4096,2048]{2,1,0:T(8,128)S(1)} all-reduce("
        "f32[4,4096,2048]{2,1,0:T(8,128)} %fusion.3), channel_id=7"
    ) == "all-reduce/psum.125"
    assert tr.op_name(
        "%all-gather-start.4 = (f32[12,8]{1,0:T(8,128)}, f32[24,8]{1,0:"
        "T(8,128)S(1)}) all-gather-start(f32[12,8]{1,0:T(8,128)} %p)"
    ) == "all-gather-start.4"
    assert tr.op_name(
        "%fusion.460 = bf16[8,128]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[8,128]"
        "{1,0:T(8,128)(2,1)} %copy-done.2), kind=kLoop, calls=%fc.9"
    ) == "fusion.460"
    assert tr.COLLECTIVE.match("all-reduce/psum.125")
