"""`readers/roofline_block_step.py` on the trace recorded on the chip
(data/chat_slice.xplane.pb: seven runs of one program in 0.7 s): the
steps' times come from the trace, their rows, columns and depths from
the engine's own counters between the `stats()` samples taken inside
the traced window, the yardstick from the configuration's architecture
(`archs/sdar_moe/costs.py: block_step`)."""

import json
import os

import pytest

from benchmarks.lib import trace_reduce as tr
from benchmarks.lib.costs import min_time
from benchmarks.lib.peaks import peaks_for
from benchmarks.lib.registry import DEFAULT_ROOT, Registry, arch_of

HERE = os.path.dirname(os.path.abspath(__file__))


def _sample(uptime, steps, rows=100, depth=900):
    return {"uptime_s": uptime, "block_steps": steps,
            "block_row_forwards": rows * steps,
            "block_columns": 4 * rows * steps,
            "attn_keys_resident": 6 * rows * (depth + 4) * steps}


@pytest.fixture(scope="module")
def obs():
    reg = Registry(DEFAULT_ROOT)
    with open(os.path.join(reg.dir, "configs",
                           "sdar-30b-a3b-pp8-d6.json")) as f:
        config = json.load(f)
    trace = tr.reduce(tr.load(os.path.join(HERE, "data",
                                           "chat_slice.xplane.pb")))
    return {"arch": arch_of(config, reg.dir), "config": config,
            "trace": trace, "replica_info": {"kind": "TPU v5 lite"},
            "t_w": 500.0, "trace_t0": 518.0, "trace_t1": 518.7,
            "stats0": _sample(30.0, 0), "stats1": _sample(75.0, 1500),
            # one sample before the traced window, three inside, one after
            "samples": [_sample(47.9, 590), _sample(48.1, 600),
                        _sample(48.4, 610), _sample(48.6, 617),
                        _sample(49.0, 640)]}


def test_the_recorded_steps_against_the_programs_counts(obs):
    reg = Registry(DEFAULT_ROOT)
    spec = reg.metric("block_step_roofline.tput")
    assert spec == {"reader": "roofline_block_step",
                    "args": {"program": "jit__paged_block_step"}}
    read = reg.reader(spec["reader"])
    # the recorded trace holds no block step: nothing to read, quietly
    assert read(obs, **spec["args"]) is None
    runs = obs["trace"]["programs"]["jit__paged_tick"]
    assert len(runs) == 7
    least = min_time(obs["arch"].block_step(obs["config"], 100, 400,
                                            100 * 900),
                     peaks_for("TPU v5 lite"))
    got = read(obs, program="jit__paged_tick")
    assert got == pytest.approx(100 * least["seconds"] * 7 / sum(runs))
    assert 0 < got < 105
    mean = obs["notes"]["jit__paged_tick_mean_step"]
    assert mean == {"rows": 100, "columns": 400, "context_tokens": 90000,
                    "steps_counted": 17, "steps_traced": 7}
    assert obs["notes"]["jit__paged_tick_bound"] == "memory"


def test_a_program_without_the_counters_reads_nothing(obs):
    read = Registry(DEFAULT_ROOT).reader("roofline_block_step")
    old = {"uptime_s": 1.0, "tokens_generated": 5}
    parent = dict(obs, samples=[], stats0=old, stats1=old)
    assert read(parent, program="jit__paged_tick") is None
    # an architecture with no block step (a body whose block is 1)
    dense = dict(obs, arch=arch_of({}, Registry(DEFAULT_ROOT).dir))
    assert read(dense, program="jit__paged_tick") is None
    assert read(dict(obs, trace=None), program="jit__paged_tick") is None
