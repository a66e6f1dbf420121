"""Model FLOP/s utilisation (%) of the train step: the FLOPs forward and
backward REQUIRE for the step's tokens (the architecture's
`train_flops_per_token`; recompute under remat not counted) over the
median traced time of `program`, the chips used and the chip's bf16
peak."""

import statistics

from benchmarks.lib.peaks import peaks_for


def read(obs, program):
    runs = ((obs.get("trace") or {}).get("programs") or {}).get(program)
    if not runs:
        return None
    job = obs["traffic"]
    flops = obs["arch"].train_flops_per_token(obs["config"], job["seq"]) \
        * job["batch"] * job["seq"]
    peak = peaks_for(obs["replica_info"]["kind"])["bf16_flops"]
    return 100 * flops / (statistics.median(runs) * obs["cell"]["chips"]
                          * peak)
