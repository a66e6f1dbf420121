"""Live KV-page migration: ship committed pages replica-to-replica.

A failover or drain used to re-prefill every token the origin replica
had already computed; this module moves the K/V pages themselves over
the transfer plane instead.  The wire discipline is TransferManager's
(PR 4), applied to engine pages:

  * the DESTINATION drives the pull: one `kv_export_begin` RPC makes
    the origin snapshot the longest cached full-page prefix of the
    request's tokens (pages pinned with an allocator incref — an
    eviction racing the migration can drop radix nodes but never
    recycle a page mid-wire), then page frames ride raw KIND_BLOB_REP
    replies straight into the destination's staging buffer through a
    `run_windowed` pump;
  * per-page integrity: a generation token minted at export (a reply
    from a stale or recycled export can never land), the transport's
    byte-length check, and a per-page CRC verified before anything
    touches the device;
  * same-host replicas skip the socket: the origin stages the export
    in a /dev/shm file the destination reads directly (the arena-mmap
    pattern), falling back to wire frames when the file is not
    reachable;
  * the destination lands pages into freshly reserved pool pages
    (engine.kv_import — a worker-thread command, so the splice happens
    between ticks, never stalling one) and only then `kv_export_end`s;
    the origin's pins release strictly after the destination sealed.

Failure semantics: any error on either side aborts the import whole —
the destination releases its reservation and re-prefills, the origin
keeps its pages (the radix tree still owns them), and the TTL sweep
reclaims export pins whose puller died.  A migrated stream is
bit-identical to an unmigrated one: pages are verbatim copies and the
resume path re-enters chunked prefill for whatever was not shipped.
"""

from __future__ import annotations

import asyncio
import logging
import os
import time
import uuid
import zlib
from typing import Dict, List, Optional, Sequence

import numpy as np

from ray_tpu._private import failpoints
from ray_tpu._private import protocol
from ray_tpu._private import tracing as _tracing
from ray_tpu._private.config import GLOBAL_CONFIG as _cfg
from ray_tpu._private.transfer import run_windowed
from ray_tpu.serve.llm.kv_tier import (frame_crc, page_frame,
                                       refuse_unframed)
from ray_tpu.util import metrics as _metrics

logger = logging.getLogger(__name__)

PAGES_MIGRATED_COUNTER = _metrics.Counter(
    "serve_kv_pages_migrated_total",
    "KV pages imported from another replica (committed to the pool)",
    tag_keys=("engine",))
MIGRATION_SECONDS = _metrics.Histogram(
    "serve_kv_migration_seconds",
    "Wall time of one KV migration pull, rendezvous to commit",
    boundaries=[0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1, 5, 30],
    tag_keys=("engine", "outcome"))

# Engine name -> engine, for inbound export requests on this process's
# core worker (two engines in one test process keep distinct names).
_SERVICES: Dict[str, "GenerationEngineRef"] = {}
GenerationEngineRef = object  # typing alias; values are engines
# xid -> export state staged by kv_export_begin.
_EXPORTS: Dict[str, Dict] = {}
# TTL sweeper task for _EXPORTS, on the core worker's event loop.
_SWEEPER: Optional["asyncio.Task"] = None
_SHM_DIR = "/dev/shm" if os.path.isdir("/dev/shm") else None


def _shm_path(xid: str) -> Optional[str]:
    return None if _SHM_DIR is None else \
        os.path.join(_SHM_DIR, f"rt_kvx_{xid}")


async def _on_worker(engine, fn, timeout: float = 30.0):
    """An engine worker command from this process's event loop: the
    command queue hands fn to the tick thread; run_in_executor keeps
    the blocking wait off the loop."""
    loop = asyncio.get_running_loop()
    return await loop.run_in_executor(
        None, lambda: engine.run_on_worker(fn, timeout=timeout))


def _ensure_sweeper() -> None:
    """Start the export-TTL sweeper on the running loop if it is not
    already alive.  A periodic task (not an inbound-traffic hook): a
    puller that dies and never triggers another kv_export_begin here
    must still have its orphaned export reclaimed — pinned pages,
    frames copy, and /dev/shm staging file all leak otherwise."""
    global _SWEEPER
    if _SWEEPER is None or _SWEEPER.done():
        _SWEEPER = asyncio.get_running_loop().create_task(_sweep_loop())


async def _sweep_loop() -> None:
    global _SWEEPER
    while True:
        await asyncio.sleep(max(0.5, _cfg.serve_kv_export_ttl_s / 4))
        now = time.monotonic()
        ttl = _cfg.serve_kv_export_ttl_s
        for xid in [x for x, e in _EXPORTS.items()
                    if now - e["t"] > ttl]:
            logger.warning("kv export %s never sealed; releasing", xid)
            await _release_export(xid)
        if not _EXPORTS:
            # Idle: retire (no awaits between the check and the reset,
            # so an export registered after this point sees a done/None
            # sweeper and starts a fresh one).
            _SWEEPER = None
            return


async def _release_export(xid: str) -> None:
    exp = _EXPORTS.pop(xid, None)
    if exp is None:
        return
    path = exp.get("path")
    if path:
        try:
            os.unlink(path)
        except OSError:
            pass
    engine = exp["engine"]
    try:
        # _on_worker, never a bare run_on_worker: this runs on the core
        # worker's RPC event loop, and the blocking wait for the tick
        # thread (a long decode tick, a first-time jit) must not stall
        # every other RPC and heartbeat behind it.
        await _on_worker(engine,
                         lambda: engine.kv_export_release(exp["pages"]))
    except Exception:
        logger.exception("kv export %s release failed", xid)


# ---------------------------------------------------------------- origin

async def _rpc_export_begin(conn, body):
    engine = _SERVICES.get(body.get("engine", ""))
    if engine is None:
        return {"error": f"no kv engine {body.get('engine')!r} here"}
    tokens = body["tokens"]
    try:
        exp = await _on_worker(engine,
                               lambda: engine.kv_export(tokens))
    except Exception as e:
        return {"error": f"export failed: {e!r}"}
    # Size the crossover on MATCHED pages (len(k)): with tiering, an
    # export can cover demoted pages that carry no pool pin, so
    # exp["pages"] undercounts what the wire would actually save.
    if exp is None or len(exp["k"]) < _cfg.serve_kv_min_migrate_pages:
        # Below the crossover the rendezvous costs more than the
        # prefill it would save: tell the puller to re-prefill.
        if exp is not None:
            await _on_worker(
                engine,
                lambda: engine.kv_export_release(exp["pages"]))
        return {"n": 0}
    k, v = exp["k"], exp["v"]
    # Same framing the tier hierarchy stores at rest (kv_tier): K bytes
    # then V bytes per page, CRC32 over the frame.
    frames = [page_frame(k[i], v[i]) for i in range(len(k))]
    xid = uuid.uuid4().hex[:12]
    gen = uuid.uuid4().hex[:12]
    path = None
    if _cfg.serve_kv_samehost:
        path = _shm_path(xid)
        if path is not None:
            try:
                with open(path, "wb") as f:
                    for fr in frames:
                        f.write(fr)
            except OSError:
                path = None
    _EXPORTS[xid] = {"engine": engine, "pages": exp["pages"],
                     "frames": frames, "gen": gen, "path": path,
                     "t": time.monotonic()}
    _ensure_sweeper()
    return {"xid": xid, "gen": gen, "n": len(frames),
            "matched_tokens": exp["matched_tokens"],
            "page_nbytes": len(frames[0]), "k_nbytes": k[0].nbytes,
            "shape_k": tuple(k.shape[1:]), "shape_v": tuple(v.shape[1:]),
            "dtype": str(k.dtype), "crc": [frame_crc(f) for f in frames],
            "path": path}


async def _rpc_fetch_page(conn, body):
    if failpoints.ACTIVE:
        act = failpoints.check("serve.kv_fetch_page")
        if act is not None:
            if act.kind == "error":
                return {"error": "failpoint: injected kv fetch error"}
            if act.kind == "delay":
                await asyncio.sleep(act.delay_s)
    exp = _EXPORTS.get(body.get("xid"))
    if exp is None or exp["gen"] != body.get("gen"):
        # Stale/recycled export: the generation check is what keeps a
        # late frame from sealing garbage into a NEW migration's pages.
        return {"error": "unknown or stale kv export"}
    # A live pull keeps its export alive: without the refresh a slow
    # (or failpoint-delayed) window could cross the TTL and get swept
    # mid-pull, failing a healthy migration into re-prefill.
    exp["t"] = time.monotonic()
    i = body["i"]
    if not 0 <= i < len(exp["frames"]):
        return {"error": f"page index {i} out of range"}
    frame = exp["frames"][i]
    return protocol.Blob({"len": len(frame), "gen": exp["gen"]},
                         memoryview(frame))


async def _rpc_export_end(conn, body):
    await _release_export(body.get("xid"))
    return {"ok": True}


def serve_exports(engine) -> None:
    """Register `engine` as an export source on this process's core
    worker (idempotent).  Handlers are process-global; the engine name
    in each request routes to the right engine."""
    _SERVICES[engine.name] = engine
    try:
        from ray_tpu._private.worker import global_worker as w
    except Exception:
        return
    if "kv_export_begin" not in w.ext_rpc:
        w.ext_rpc["kv_export_begin"] = _rpc_export_begin
        w.ext_rpc["kv_fetch_page"] = _rpc_fetch_page
        w.ext_rpc["kv_export_end"] = _rpc_export_end


def rendezvous(engine) -> Optional[Dict]:
    """This replica's pull address: (host, port) of its core worker's
    RPC server plus the engine name.  Rides load gauges and resume
    cursors so a peer (or the router) can point a migration here.
    None outside a cluster (no worker server to pull from)."""
    serve_exports(engine)
    try:
        from ray_tpu._private.worker import global_worker as w
        addr = w.addr
    except Exception:
        return None
    if addr is None:
        return None
    return {"host": addr[0], "port": int(addr[1]),
            "engine": engine.name}


# ----------------------------------------------------------- destination

async def pull_kv_pages(rdv: Dict, tokens: Sequence[int], engine,
                        timeout: float = 30.0) -> int:
    """Pull the K/V pages an origin replica holds for `tokens` into
    `engine`'s pool.  Returns the number of pages imported; 0 means
    re-prefill (origin had nothing worth shipping, the pool is too hot
    to host the import, or the transfer failed — the pool is NEVER
    left referencing partial data).  An engine whose model keeps
    per-row state is refused: its pages are not a sequence's state."""
    refuse_unframed(engine.cfg, "a KV migration (pull_kv_pages)")
    t0 = time.monotonic()
    with _tracing.span("serve", "serve.kv_migrate",
                       args={"engine": engine.name,
                             "origin": f"{rdv.get('host')}:"
                                       f"{rdv.get('port')}"}) as h:
        imported = 0
        outcome = "failed"
        try:
            imported = await _pull_impl(rdv, tokens, engine, timeout)
            outcome = "imported" if imported else "skipped"
        except asyncio.CancelledError:
            raise
        except Exception as e:
            logger.warning("kv migration from %s:%s failed (%r); "
                           "falling back to re-prefill",
                           rdv.get("host"), rdv.get("port"), e)
        h.args["pages"] = imported
        h.args["outcome"] = outcome
        MIGRATION_SECONDS.observe(
            time.monotonic() - t0,
            tags={"engine": engine.name, "outcome": outcome})
        if imported:
            PAGES_MIGRATED_COUNTER.inc(
                imported, tags={"engine": engine.name})
        return imported


async def _pull_impl(rdv: Dict, tokens: Sequence[int], engine,
                     timeout: float) -> int:
    tokens = [int(t) for t in tokens]
    if len(tokens) // engine.page_size < _cfg.serve_kv_min_migrate_pages:
        return 0  # can't clear the crossover even on a full match
    conn = await protocol.Connection.connect(
        rdv["host"], rdv["port"], name="kv-migrate",
        timeout=min(timeout, _cfg.connect_timeout_s))
    xid = None
    try:
        meta = await conn.request(
            "kv_export_begin",
            {"engine": rdv.get("engine", "default"), "tokens": tokens},
            timeout=timeout)
        if not isinstance(meta, dict) or meta.get("error") \
                or not meta.get("n"):
            return 0
        xid = meta["xid"]
        n, nb = meta["n"], meta["page_nbytes"]
        buf = bytearray(n * nb)
        mv = memoryview(buf)
        if not _read_samehost(meta, mv):
            await _pull_wire(conn, meta, mv, timeout)
        crcs = meta["crc"]
        for i in range(n):
            if zlib.crc32(mv[i * nb:(i + 1) * nb]) != crcs[i]:
                raise RuntimeError(f"kv page {i} CRC mismatch")
        dt = np.dtype(meta["dtype"])
        knb = meta["k_nbytes"]
        kshape, vshape = tuple(meta["shape_k"]), tuple(meta["shape_v"])
        k = np.empty((n,) + kshape, dt)
        v = np.empty((n,) + vshape, dt)
        for i in range(n):
            base = i * nb
            k[i] = np.frombuffer(
                mv[base:base + knb], dt).reshape(kshape)
            v[i] = np.frombuffer(
                mv[base + knb:base + nb], dt).reshape(vshape)
        matched = tokens[:meta["matched_tokens"]]
        return await _on_worker(
            engine, lambda: engine.kv_import(matched, k, v),
            timeout=timeout)
    finally:
        if xid is not None:
            # Seal (or abort): ONLY now may the origin drop its pins.
            try:
                await conn.request("kv_export_end", {"xid": xid},
                                   timeout=5)
            except Exception:
                pass  # origin's TTL sweep reclaims the export
        try:
            await conn.close()
        except Exception:
            pass


def _read_samehost(meta: Dict, mv: memoryview) -> bool:
    """Same-host fast path: the origin's staging file read directly
    (one memcpy off /dev/shm).  Any miss — no path, file gone, size
    mismatch — falls back to the wire."""
    path = meta.get("path")
    if not path or not _cfg.serve_kv_samehost:
        return False
    try:
        with open(path, "rb") as f:
            data = f.read()
    except OSError:
        return False
    if len(data) != len(mv):
        return False
    mv[:] = data
    return True


async def _pull_wire(conn, meta: Dict, mv: memoryview,
                     timeout: float) -> None:
    n, nb = meta["n"], meta["page_nbytes"]

    def maker(i):
        async def go():
            rep = await conn.request_blob(
                "kv_fetch_page",
                {"xid": meta["xid"], "i": i, "gen": meta["gen"]},
                mv[i * nb:(i + 1) * nb], timeout=timeout)
            if isinstance(rep, dict) and rep.get("error"):
                raise RuntimeError(str(rep["error"]))
            got = rep.get("len") if isinstance(rep, dict) else None
            if got != nb:
                # A short delivery fills only a prefix of the slice;
                # counting it done would seal garbage in the tail.
                raise RuntimeError(f"short kv page: {got} of {nb}")
        return go

    await run_windowed([maker(i) for i in range(n)],
                       max(1, _cfg.serve_kv_migration_window_chunks))


# ------------------------------------------------------------- local path

def migrate_local(src_engine, dst_engine, tokens: Sequence[int],
                  timeout: float = 30.0) -> int:
    """Same-process migration (two engines, one host): the export's
    host staging array hands straight to the import — the same
    pin/commit/seal sequence as the wire path minus the frames.  Used
    by in-process tests and the bench's crossover leg; returns pages
    imported (0 = re-prefill)."""
    for eng in (src_engine, dst_engine):
        refuse_unframed(eng.cfg, "a KV migration (migrate_local)")
    tokens = [int(t) for t in tokens]
    exp = src_engine.run_on_worker(
        lambda: src_engine.kv_export(tokens), timeout=timeout)
    if exp is None:
        return 0
    try:
        if len(exp["k"]) < _cfg.serve_kv_min_migrate_pages:
            return 0
        matched = tokens[:exp["matched_tokens"]]
        n = dst_engine.run_on_worker(
            lambda: dst_engine.kv_import(matched, exp["k"], exp["v"]),
            timeout=timeout)
        if n:
            PAGES_MIGRATED_COUNTER.inc(
                n, tags={"engine": dst_engine.name})
        return n
    finally:
        src_engine.run_on_worker(
            lambda: src_engine.kv_export_release(exp["pages"]),
            timeout=timeout)
