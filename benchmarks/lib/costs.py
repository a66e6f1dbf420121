"""What every architecture's yardstick shares: the width of the served
type, and the least time the chip could take for a call's needed
operations and bytes.  The operations and bytes themselves are counted
by the configuration's architecture (`archs/<arch>`: `decode_tick`,
`prefill_chunk`, `train_flops_per_token`, ...), which the readers reach
through `obs["arch"]`.
"""

from __future__ import annotations

from typing import Dict

BF16 = 2


def min_time(cost: Dict, peaks: Dict) -> Dict:
    """The least time the chip could take, and which peak bounds it."""
    t_f = cost["flops"] / peaks["bf16_flops"]
    t_b = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_f, t_b),
            "bound": "compute" if t_f > t_b else "memory"}
