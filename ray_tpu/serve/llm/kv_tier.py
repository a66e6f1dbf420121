"""Cold-tier backing for the KV memory hierarchy (T1 arena, T2 store).

The engine's three tiers:

  T0  decode pool — pages live on device, addressed through block
      tables (paging.py owns the accounting);
  T1  HostKVArena — one /dev/shm-backed mmap per engine, fixed-size
      page slots over a byte budget (the same arena-mmap pattern the
      transfer plane's same-host path uses).  Fast demote/promote, dies
      with the process;
  T2  KVPageStore — a host-shared spill directory of content-addressed
      page files plus session manifests.  Survives replica death; any
      replica on the host can import from it — which is exactly what
      makes a durable session resurrect anywhere.

Integrity discipline is kv_transfer's, applied at rest: every page
travels as one frame (K bytes + V bytes, `page_frame`), every frame
carries a CRC32 checked before anything touches the device, and a store
write is temp-file + rename so a reader can never observe a torn page.
A failed read is a MISS (the caller re-prefills), never a corrupt
import — the all-or-nothing bar migration set applies to tiers too.

Single-owner discipline: the arena's slot list and every tree-facing
call belong to the engine's worker thread (the store's files are
additionally shared across processes, which the atomic-rename write
makes safe).  The host half of a demotion — device-to-host copy, CRC,
the bytes' landing in a slot the worker reserved or in a store file —
runs on the engine's PageLander thread, which touches neither the radix
tree nor the allocator nor the slot list: it posts what landed where
and the worker commits it.
"""

from __future__ import annotations

import functools
import json
import logging
import mmap
import os
import queue
import struct
import tempfile
import threading
import time
import uuid
import zlib
from typing import Dict, List, Optional

import numpy as np

from ray_tpu._private import tracing as _tracing
from ray_tpu.models import decode

logger = logging.getLogger(__name__)

_SHM_DIR = "/dev/shm" if os.path.isdir("/dev/shm") else None
# Store page/manifest file header: magic, CRC32 of the body, body length.
_HDR = struct.Struct("<4sII")
_MAGIC = b"rtkv"


def refuse_row_state(cfg, what: str) -> None:
    """A frame (page_frame below, and kv_transfer's on the wire) is K
    then V of every layer for one page: the whole of a sequence's state
    for the models the tiers, the prefix cache, migration and session
    checkpoints were built for.  A model that also keeps a recurrent
    state per decode row (its body declares `row_state_keys`) cannot be
    carried that way: sharing or restoring a prefix would need the state
    as it stood at the prefix's last page boundary, which nothing
    snapshots yet.  Refused by name rather than served wrong; a no-op
    for a model whose pages are all of its state."""
    if not decode.paged_body(cfg).has_row_state:
        return
    raise NotImplementedError(
        f"{what} on a model with per-row recurrent state "
        f"({type(cfg).__name__}): a page is not the whole of a sequence's "
        f"state there; missing: state snapshots at page boundaries")


def refuse_unframed(cfg, what: str) -> None:
    """Everything that frames pages (this file's tiers, kv_export /
    kv_import, migration, session checkpoints) calls this first: it
    refuses a model with per-row state (refuse_row_state) and a model
    whose pages are not K then V of [page, Hkv, Dh] at all (its body is
    not `framed`: a latent page), by name, until a page is opaque bytes
    of a size the model declares; a model that is both is told both.  The radix prefix cache only hands out
    page ids and serves such a model as it is."""
    body = decode.paged_body(cfg)
    if body.has_row_state and body.page_keys != ("k", "v"):
        # both at once (models/bailing_hybrid.py): say both
        raise NotImplementedError(
            f"{what} on a model with per-row recurrent state "
            f"({type(cfg).__name__}) whose pages are not K then V either "
            f"(a latent page): a page is not the whole of a sequence's "
            f"state there, and tiers and the wire frame K-then-V of "
            f"[page, Hkv, Dh]; missing: state snapshots at page "
            f"boundaries, and a page as opaque bytes of a size the model "
            f"declares")
    refuse_row_state(cfg, what)
    if body.framed:
        return
    if body.block > 1:
        raise NotImplementedError(
            f"{what} on a model that generates by diffusion over blocks "
            f"({type(cfg).__name__}): a row's last block is rewritten "
            f"until every position of it is fixed, so a page that holds "
            f"it is not final; missing: a frame that stops at the last "
            f"block whose keys are final")
    raise NotImplementedError(
        f"{what} on a model whose pages are not K then V "
        f"({type(cfg).__name__}: a latent page): tiers and the wire frame "
        f"K-then-V of [page, Hkv, Dh]; missing: a page as opaque bytes of "
        f"a declared size")


def page_frame(k_page: np.ndarray, v_page: np.ndarray) -> bytes:
    """One page's wire/at-rest frame: K bytes then V bytes, contiguous.
    The SAME framing kv_transfer puts on migration frames, so a tier
    and a peer replica are interchangeable sources for an import."""
    return k_page.tobytes() + v_page.tobytes()


def frame_crc(frame) -> int:
    """CRC32 of a frame: bytes, or the contiguous uint8 row of a host
    stack that IS the frame (decode.paged_read_pages).  zlib drops the
    GIL for anything over a few KiB."""
    return zlib.crc32(frame)


def split_frame(frame: bytes, k_nbytes: int, kshape, vshape,
                dtype) -> tuple:
    """Inverse of page_frame: (k, v) arrays of the given shapes."""
    k = np.frombuffer(frame[:k_nbytes], dtype).reshape(kshape)
    v = np.frombuffer(frame[k_nbytes:], dtype).reshape(vshape)
    return k, v


class HostKVArena:
    """Fixed-slot host arena for demoted KV pages (tier T1).

    One mmap of capacity * page_nbytes bytes, /dev/shm-backed when
    available (anonymous otherwise — same lifetime, no name).  Slots
    are recycled LIFO; the caller (the radix trie's payload) records
    which slot holds which page plus its CRC — the arena itself is
    deliberately dumb storage."""

    def __init__(self, page_nbytes: int, budget_bytes: int,
                 name: str = "default"):
        if page_nbytes < 1:
            raise ValueError("page_nbytes must be >= 1")
        self.page_nbytes = int(page_nbytes)
        self.capacity = max(1, int(budget_bytes) // self.page_nbytes)
        size = self.capacity * self.page_nbytes
        self._path: Optional[str] = None
        if _SHM_DIR is not None:
            self._path = os.path.join(
                _SHM_DIR, f"rt_kvarena_{name}_{uuid.uuid4().hex[:8]}")
            try:
                with open(self._path, "wb") as f:
                    f.truncate(size)
                self._file = open(self._path, "r+b")
                self._mm = mmap.mmap(self._file.fileno(), size)
            except OSError:
                self._path = None
        if self._path is None:
            self._file = None
            self._mm = mmap.mmap(-1, size)
        self._view = np.frombuffer(self._mm, np.uint8)
        self._free: List[int] = list(range(self.capacity - 1, -1, -1))
        self._closed = False

    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def used_slots(self) -> int:
        return self.capacity - len(self._free)

    def reserve(self) -> Optional[int]:
        """Take a free slot (worker thread: the slot list is its own) or
        None when the budget is spent (the sweeper then demotes to the
        store tier instead — the arena is a cache over T2, never a hard
        wall).  The bytes follow through write(), from any one thread."""
        if self._closed or not self._free:
            return None
        return self._free.pop()

    def write(self, slot: int, frame) -> bool:
        """Land one page frame (bytes or a contiguous uint8 array) in a
        reserved slot.  Copied through a numpy view of the mmap, which
        releases the GIL for the copy's length: a slice assignment into
        the mmap holds it (2.8 ms a 1 MiB page under gVisor)."""
        if self._closed or len(frame) != self.page_nbytes:
            return False
        base = slot * self.page_nbytes
        np.copyto(self._view[base:base + self.page_nbytes],
                  np.frombuffer(frame, np.uint8))
        return True

    def put(self, frame) -> Optional[int]:
        """reserve() + write(): stage one page frame; its slot, or None
        when the budget is spent or the frame is not a page."""
        if len(frame) != self.page_nbytes:
            return None
        slot = self.reserve()
        if slot is not None:
            self.write(slot, frame)
        return slot

    def get(self, slot: int) -> Optional[bytes]:
        if self._closed or not 0 <= slot < self.capacity:
            return None
        base = slot * self.page_nbytes
        return bytes(self._mm[base:base + self.page_nbytes])

    def free(self, slot: int) -> None:
        if not self._closed and 0 <= slot < self.capacity \
                and slot not in self._free:
            self._free.append(slot)

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._view = None       # the view pins the mmap's buffer
        try:
            self._mm.close()
        except (OSError, ValueError, BufferError):
            pass
        if self._file is not None:
            try:
                self._file.close()
            except OSError:
                pass
        if self._path:
            try:
                os.unlink(self._path)
            except OSError:
                pass


class PageLander:
    """The host half of a demotion, off the engine's tick thread.

    The worker thread dispatches the device gather of the pages it
    demotes (decode.paged_read_stack), reserves where each page
    goes — an arena slot, or a store fingerprint when the arena is
    spent — and submit()s the still-running device stack with those
    entries.  This one daemon thread then does what touches host bytes:
    the device-to-host copy, each page's CRC (a row of the host stack is
    the page's frame byte for byte), the arena write or the store file.
    It touches neither the radix tree nor the allocator nor the arena's
    slot list: for every entry it appends
    `(node, ticket, slot, payload | None)` to `landed`, which the worker
    drains and commits (`payload` None: nowhere to land).  Every copy
    here releases the GIL, so the thread that dispatches ticks is not
    handed this one's milliseconds.

    Device stacks awaiting their copy are counted in bytes: the worker
    waits in wait_room() past its cap and in drain() when a caller needs
    every page landed (flush, the forced sweep, shutdown).  call() queues
    other host work of the tiers behind them (the store's TTL sweep)."""

    def __init__(self, name: str, landed):
        self.landed = landed            # deque: append here, worker pops
        self._jobs: "queue.SimpleQueue" = queue.SimpleQueue()
        self._cond = threading.Condition()
        self._pending = 0               # jobs submitted and not finished
        self._bytes = 0                 # device bytes not yet copied
        self.busy_s = 0.0               # this thread's working seconds
        self._thread = threading.Thread(
            target=self._run, name=f"llm-lander-{name}", daemon=True)
        self._thread.start()

    def submit(self, stack, entries, arena, store) -> None:
        """Queue one dispatched gather: `entries[i]` =
        (node, ticket, slot | None, fingerprint | None) for row i of
        `stack`; rows past len(entries) are padding."""
        self._enqueue(functools.partial(
            self._land, [stack, entries, arena, store]), stack.nbytes)

    def call(self, fn, *args) -> None:
        """Queue other host work of the tiers that needs no answer (the
        store's TTL sweep: a listing of thousands of files)."""
        self._enqueue(functools.partial(fn, *args), 0)

    def _enqueue(self, job, nbytes: int) -> None:
        with self._cond:
            self._pending += 1
            self._bytes += nbytes
        self._jobs.put(job)

    def wait_room(self, nbytes: int, cap: int) -> None:
        """Block until `nbytes` more fit under `cap` bytes of device
        stacks awaiting their copy (one stack always fits)."""
        with self._cond:
            while self._bytes and self._bytes + nbytes > cap:
                self._cond.wait()

    def drain(self) -> None:
        """Block until every submitted job has been landed."""
        with self._cond:
            while self._pending:
                self._cond.wait()

    def close(self, timeout: float = 30.0) -> None:
        """Land what is queued, then end the thread."""
        self._jobs.put(None)
        self._thread.join(timeout)

    def _run(self):
        while True:
            job = self._jobs.get()
            if job is None:
                return
            t0 = time.monotonic()
            try:
                job()
            except BaseException:
                logger.exception("kv lander: %r failed", job)
            self.busy_s += time.monotonic() - t0
            with self._cond:
                self._pending -= 1
                self._cond.notify_all()

    def _land(self, job):
        stack, entries, arena, store = job
        job[0] = None       # this frame's `stack` is the last reference
        wall0, t0 = time.time(), time.monotonic()
        posted = 0
        crc_s = put_s = 0.0
        nbytes = stack.nbytes
        try:
            try:
                host = np.asarray(stack)    # the device-to-host copy
            finally:
                stack = None                # ...frees the device stack
                with self._cond:
                    self._bytes -= nbytes
                    self._cond.notify_all()
            frames = host.reshape(len(host), -1).view(np.uint8)
            t1 = time.monotonic()
            for node, ticket, slot, fp in entries:
                frame = frames[posted]
                ta = time.monotonic()
                crc = frame_crc(frame)
                tb = time.monotonic()
                if slot is not None:
                    ok = arena.write(slot, frame)
                    payload = ("t1", slot, crc, len(frame))
                else:
                    ok = store is not None \
                        and store.put_page(fp, frame, crc)
                    payload = ("t2", fp, crc, len(frame))
                crc_s += tb - ta
                put_s += time.monotonic() - tb
                self.landed.append(
                    (node, ticket, slot, payload if ok else None))
                posted += 1
        finally:
            # Whatever failed, every entry gets its verdict: the worker
            # frees the slot and drops the node.
            for node, ticket, slot, _fp in entries[posted:]:
                self.landed.append((node, ticket, slot, None))
        _tracing.record(
            "engine", "engine.tier_land", wall0, time.monotonic() - t0,
            args={"pages": len(entries),
                  "copy_ms": round((t1 - t0) * 1e3, 3),
                  "frame_ms": round(crc_s * 1e3, 3),
                  "put_ms": round(put_s * 1e3, 3)})


def default_store_dir() -> str:
    """The host-shared spill directory every engine on this host
    agrees on (uid-scoped, the tempdir convention): config's
    serve_kv_store_dir when set, else <tempdir>/rt_kv_store-<uid>."""
    from ray_tpu._private.config import GLOBAL_CONFIG as _cfg
    configured = getattr(_cfg, "serve_kv_store_dir", "") or ""
    if configured:
        return configured
    uid = os.getuid() if hasattr(os, "getuid") else 0
    return os.path.join(tempfile.gettempdir(), f"rt_kv_store-{uid}")


def _atomic_write(path: str, *parts) -> bool:
    """temp + rename so a concurrent reader (another replica pulling a
    resurrecting session) can never observe a torn file.  `parts` are
    written one after the other (a header and a page are never joined
    in memory first)."""
    tmp = f"{path}.tmp.{uuid.uuid4().hex[:8]}"
    try:
        with open(tmp, "wb") as f:
            for part in parts:
                f.write(part)
        os.replace(tmp, path)
        return True
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


def _checked_read(path: str) -> Optional[bytes]:
    """Read one header-framed file; any miss — absent, torn, CRC
    mismatch — is None, and a corrupt file is unlinked so it cannot
    keep failing future reads."""
    try:
        with open(path, "rb") as f:
            data = f.read()
    except OSError:
        return None
    if len(data) < _HDR.size:
        return None
    magic, crc, n = _HDR.unpack_from(data)
    body = data[_HDR.size:]
    if magic != _MAGIC or len(body) != n or zlib.crc32(body) != crc:
        logger.warning("kv store entry %s failed integrity check; "
                       "dropping it", os.path.basename(path))
        try:
            os.unlink(path)
        except OSError:
            pass
        return None
    return body


class KVPageStore:
    """Durable page + session-manifest store (tier T2).

    Layout under `root`:
      pages/<fp>.kv        one page frame, content-addressed by the
                           chained prefix fingerprint of the page's
                           full prefix (two replicas that never spoke
                           agree on the key — paging.prefix_fingerprints)
      sessions/<id>.json   session manifest: token history, sampler RNG
                           state, page fingerprint chain, timestamp

    Every file is CRC-framed and atomically replaced; reads validate
    before returning.  sweep() ages both kinds out by mtime."""

    def __init__(self, root: Optional[str] = None):
        self.root = root or default_store_dir()
        self._pages = os.path.join(self.root, "pages")
        self._sessions = os.path.join(self.root, "sessions")
        for d in (self._pages, self._sessions):
            os.makedirs(d, exist_ok=True)

    # -- pages ---------------------------------------------------------

    def _page_path(self, fp: str) -> str:
        return os.path.join(self._pages, f"{fp}.kv")

    def put_page(self, fp: str, frame, crc: Optional[int] = None) -> bool:
        """Store one page frame (bytes or a contiguous uint8 array)
        under its fingerprint; `crc` is frame_crc(frame) where the
        caller has it already."""
        path = self._page_path(fp)
        if os.path.exists(path):
            # Content-addressed: an existing entry is the same bytes
            # (deterministic prefill), so rewriting buys nothing.
            return True
        if crc is None:
            crc = zlib.crc32(frame)
        return _atomic_write(path, _HDR.pack(_MAGIC, crc, len(frame)),
                             frame)

    def get_page(self, fp: str) -> Optional[bytes]:
        return _checked_read(self._page_path(fp))

    def has_page(self, fp: str) -> bool:
        return os.path.exists(self._page_path(fp))

    # -- session manifests ---------------------------------------------

    def _session_path(self, session_id: str) -> str:
        safe = "".join(c if c.isalnum() or c in "-_." else "_"
                       for c in str(session_id))[:128]
        return os.path.join(self._sessions, f"{safe}.json")

    def put_session(self, session_id: str, manifest: Dict) -> bool:
        body = json.dumps(manifest).encode()
        hdr = _HDR.pack(_MAGIC, zlib.crc32(body), len(body))
        return _atomic_write(self._session_path(session_id), hdr, body)

    def get_session(self, session_id: str) -> Optional[Dict]:
        body = _checked_read(self._session_path(session_id))
        if body is None:
            return None
        try:
            return json.loads(body)
        except ValueError:
            return None

    # -- hygiene -------------------------------------------------------

    def stats(self) -> Dict[str, int]:
        try:
            return {"pages": len(os.listdir(self._pages)),
                    "sessions": len(os.listdir(self._sessions))}
        except OSError:
            return {"pages": 0, "sessions": 0}

    def sweep(self, ttl_s: float) -> int:
        """Drop entries untouched for ttl_s (mtime); returns how many.
        Both sweeping engines racing on one shared directory is fine —
        unlink of an already-gone file is a no-op."""
        cutoff = time.time() - ttl_s
        dropped = 0
        for d in (self._pages, self._sessions):
            try:
                names = os.listdir(d)
            except OSError:
                continue
            for name in names:
                path = os.path.join(d, name)
                try:
                    if os.path.getmtime(path) < cutoff:
                        os.unlink(path)
                        dropped += 1
                except OSError:
                    pass
        return dropped
