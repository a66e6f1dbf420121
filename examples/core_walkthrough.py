"""Core API walkthrough: tasks, actors, objects, placement groups.

Run: JAX_PLATFORMS=cpu python examples/core_walkthrough.py
(reference analogue: the ray-core walkthrough examples)
"""

import numpy as np

import ray_tpu


def main():
    ray_tpu.init(num_cpus=4)

    # --- tasks
    @ray_tpu.remote
    def square(x):
        return x * x

    print("squares:", ray_tpu.get([square.remote(i) for i in range(5)]))

    # --- objects through the shared-memory store (zero-copy numpy)
    big = np.random.rand(1000, 1000)
    ref = ray_tpu.put(big)
    assert ray_tpu.get(ref).shape == (1000, 1000)
    print("put/get of %.1f MB ok" % (big.nbytes / 1e6))

    # --- actors
    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.n = 0

        def incr(self, k=1):
            self.n += k
            return self.n

    c = Counter.remote()
    # Submit all three first — actor tasks run in submission order, so
    # one batched get returns [1, 2, 3] without three round trips.
    print("counter:", ray_tpu.get([c.incr.remote() for _ in range(3)]))

    # --- placement group: reserve a resource bundle, run inside it
    from ray_tpu.util.placement_group import (placement_group,
                                              remove_placement_group)
    from ray_tpu.util.scheduling_strategies import (
        PlacementGroupSchedulingStrategy)

    pg = placement_group([{"CPU": 1}], strategy="PACK")
    ray_tpu.wait_placement_group_ready(pg)
    strat = PlacementGroupSchedulingStrategy(placement_group=pg)
    print("in-pg task:",
          ray_tpu.get(square.options(scheduling_strategy=strat).remote(7)))
    remove_placement_group(pg)

    ray_tpu.shutdown()
    print("core walkthrough done")


if __name__ == "__main__":
    main()
