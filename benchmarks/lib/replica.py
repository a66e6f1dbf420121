"""The serving replica under test, with the benchmark's probes added.

`BenchLLMServer` IS `serve.llm.LLMServer` — same constructor, engine,
scheduler, cache and streaming transport — plus methods that only the
process holding the chip can answer: start and stop a device trace,
count compile-cache entries, read the device's memory, and compare the
engine's own jitted prefill-chunk and decode-tick programs, run through
the engine's own page pool, with the plain reference.
"""

from __future__ import annotations

import os
from typing import Any, Dict

import numpy as np

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
        """Prefill `prompt_len` seeded tokens chunk by chunk, then decode
        `n_decode` greedy tokens tick by tick, through the engine's own
        programs and pool (between ticks, on the engine's worker thread);
        compare every position's logits with one full forward of the
        plain reference of `config`'s architecture over the same
        tokens."""
        import jax
        import jax.numpy as jnp

        from benchmarks.lib.registry import arch_of
        from ray_tpu.serve.llm import engine as engine_mod

        reference = arch_of(config, bench_dir).reference

        eng, cfg = self.engine, self.engine.cfg
        rng = np.random.default_rng([int(seed), 0xC0FFEE])
        prompt = rng.integers(1, cfg.vocab_size, size=prompt_len)

        def through_the_engine():
            if any(r is not None for r in eng._slots):
                raise RuntimeError("the engine is not idle")
            n_pages = -(-(prompt_len + n_decode) // eng.page_size)
            pages = eng._alloc.alloc(n_pages)
            if pages is None and eng._prefix is not None:
                eng._prefix.evict(n_pages)      # as admission would
                pages = eng._alloc.alloc(n_pages)
            if pages is None:
                raise RuntimeError("no free pages for the logits check")
            try:
                bt_row = np.zeros((eng._max_blocks,), np.int32)
                bt_row[:n_pages] = pages
                rows = []
                for start in range(0, prompt_len, eng.prefill_chunk):
                    width = min(eng.prefill_chunk, eng._s_virt - start)
                    real = prompt[start:start + width]
                    chunk = np.zeros((1, width), np.int32)
                    chunk[0, :len(real)] = real
                    logits, eng._cache = engine_mod._prefill_chunk(
                        eng.params, jnp.asarray(chunk), jnp.int32(start),
                        eng._cache, jnp.asarray(bt_row[None, :]), cfg)
                    rows.append(np.asarray(logits[0, :len(real)]))
                tokens = [int(rows[-1][-1].argmax())]
                bt = np.zeros_like(eng._block_tables)
                bt[0] = bt_row
                pos = np.zeros((eng.num_slots,), np.int32)
                tok = np.zeros((eng.num_slots,), np.int32)
                for i in range(n_decode):
                    pos[0], tok[0] = prompt_len + i, tokens[-1]
                    sampled, logits, eng._cache = engine_mod._paged_tick(
                        eng.params, jnp.asarray(tok), jnp.asarray(pos),
                        eng._cache, jnp.asarray(bt), cfg, with_logits=True)
                    rows.append(np.asarray(logits[:1]))
                    tokens.append(int(np.asarray(sampled)[0]))
                return np.concatenate(rows), tokens
            finally:
                for p in pages:
                    eng._alloc.decref(p)

        got, tokens = eng.run_on_worker(through_the_engine, timeout=900.0)
        seq = np.concatenate([prompt, tokens[:n_decode]]).astype(np.int32)
        ref = np.asarray(jax.jit(
            lambda params, tokens: reference(params, tokens, config))(
                eng.params, jnp.asarray(seq)))
        diff = np.abs(got - ref)
        return {"positions": int(len(seq)), "prefill_positions": prompt_len,
                "decode_positions": n_decode,
                "finite": bool(np.isfinite(got).all()
                               and np.isfinite(ref).all()),
                "max_abs_diff": float(diff.max()),
                "max_abs_diff_prefill": float(diff[:prompt_len].max()),
                "max_abs_diff_decode": float(diff[prompt_len:].max()),
                "mean_abs_diff": float(diff.mean()),
                "reference_logit_std": float(ref.std()),
                "argmax_equal": int((got.argmax(-1) == ref.argmax(-1)).sum())}


def bench_deployment(model_loader, *, engine_config: Dict,
                     ray_actor_options: Dict, name: str = "llm"):
    """What `serve.llm.llm_deployment` builds, of the probed class."""
    from ray_tpu.serve.api import deployment
    dep = deployment(BenchLLMServer, name=name, num_replicas=1,
                     max_concurrent_queries=256,
                     ray_actor_options=ray_actor_options)
    return dep.options(init_args=(model_loader, engine_config, None))
