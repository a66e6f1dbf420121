"""The serving replica under test, with the benchmark's probes added.

`BenchLLMServer` IS `serve.llm.LLMServer` — same constructor, engine,
scheduler, cache and streaming transport — plus methods that only the
process holding the chip can answer: start and stop a device trace,
count compile-cache entries, read the device's memory, and run the
logits check of the configuration's architecture (lib/checks.py).
"""

from __future__ import annotations

import os
from typing import Any, Dict

from benchmarks.lib import probes
from ray_tpu.serve.llm.api import LLMServer


class BenchLLMServer(LLMServer):

    def probe_cache_entries(self) -> int:
        return probes.cache_entries()

    def probe_host_files(self) -> list:
        """Host-arena files (`/dev/shm/rt_kvarena_*`) this replica holds:
        the program unlinks them only in `close()`, which a replica
        killed with its lease never reaches, so the run removes them."""
        held = set()
        for fd in os.listdir("/proc/self/fd"):
            try:
                target = os.readlink(f"/proc/self/fd/{fd}")
            except OSError:
                continue
            if os.path.basename(target).startswith("rt_kvarena_"):
                held.add(target.replace(" (deleted)", ""))
        return sorted(held)

    def probe_trace_start(self, trace_dir: str) -> bool:
        probes.start_trace(trace_dir)
        return True

    def probe_trace_stop(self) -> bool:
        import jax
        jax.profiler.stop_trace()
        return True

    def probe_check_logits(self, seed: int, prompt_len: int,
                           n_decode: int, config: Dict[str, Any],
                           bench_dir: str) -> Dict[str, Any]:
        """The logits check of `config`'s architecture: its module's
        `check_logits` where it brings one, `checks.default` (a prefill
        chunk by chunk, then one token a row a tick) where it does not,
        held to the contract `lib/checks.py` states."""
        from benchmarks.lib import checks
        from benchmarks.lib.registry import arch_of

        arch = arch_of(config, bench_dir)
        procedure = arch.check_logits if hasattr(arch, "check_logits") \
            else checks.default
        return checks.hold(
            procedure(self.engine, seed, prompt_len, n_decode, config,
                      arch.reference),
            procedure, config.get("arch", "llama"))


def bench_deployment(model_loader, *, engine_config: Dict,
                     ray_actor_options: Dict, name: str = "llm"):
    """What `serve.llm.llm_deployment` builds, of the probed class."""
    from ray_tpu.serve.api import deployment
    dep = deployment(BenchLLMServer, name=name, num_replicas=1,
                     max_concurrent_queries=256,
                     ray_actor_options=ray_actor_options)
    return dep.options(init_args=(model_loader, engine_config, None))
