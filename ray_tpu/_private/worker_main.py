"""Entry point of a worker process spawned by the raylet.

Reference: python/ray/_private/workers/default_worker.py — connects the
core worker to its raylet + GCS and runs the task loop until told to exit.
"""

from __future__ import annotations

import asyncio
import logging
import os
import time


async def _amain():
    from ray_tpu._private import tracing
    t_proc = tracing.process_start()
    if os.environ.get("JAX_PLATFORMS") == "tpu":
        # A TPU worker (raylet._worker_env_for) is the one kind of
        # process that compiles for the chip.
        from ray_tpu._private.jax_utils import enable_compile_cache
        enable_compile_cache()
    from ray_tpu._private import worker as worker_mod
    from ray_tpu._private.ids import WorkerID
    from ray_tpu._private.worker import CoreWorker, MODE_WORKER

    t_imported = time.time()
    gcs_addr = (os.environ["RT_GCS_HOST"], int(os.environ["RT_GCS_PORT"]))
    raylet_addr = (os.environ["RT_RAYLET_HOST"],
                   int(os.environ["RT_RAYLET_PORT"]))
    # Workers advertise their node's address (the raylet's bind host):
    # on multi-host clusters, peers dial workers directly for task push
    # and owner-protocol calls, and loopback would not route.
    host = raylet_addr[0]
    cw = CoreWorker(
        MODE_WORKER,
        gcs_addr,
        raylet_addr=raylet_addr,
        store_path=os.environ.get("RT_STORE_PATH"),
        store_cap=int(os.environ.get("RT_STORE_CAP", "0")) or None,
        worker_id=WorkerID.from_hex(os.environ["RT_WORKER_ID"]),
        host=host,
    )
    worker_mod.global_worker = cw
    # The boot, for the books: it ends when this worker is ready for
    # its first task (or when that task comes, if it comes first), and
    # its span (worker.boot) is made when the first actor brings a trace
    # to link it into (tracing.start_begin).
    tracing.start_note("boot", (t_proc, None))
    tracing.start_note("import_s", t_imported - t_proc)
    await cw.start_worker_async()
    tracing.start_ready()
    await asyncio.Event().wait()


def main():
    logging.basicConfig(
        level=logging.INFO,
        format=f"[worker {os.getpid()}] %(levelname)s %(message)s")
    try:
        asyncio.run(_amain())
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
