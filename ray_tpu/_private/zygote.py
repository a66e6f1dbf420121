"""Zygote: fork-based fast worker spawn.

A Python worker cold-start on this runtime costs ~2s (interpreter boot +
imports).  The reference amortizes process starts with a
prestarted worker pool (reference: src/ray/raylet/worker_pool.h:153
PrestartWorkers / maximum_startup_concurrency), but a pool can't keep up
with actor-launch storms where every actor consumes a fresh process.  The
zygote pays the import cost ONCE per node: the raylet spawns this process at
startup, it preloads the worker stack, and every subsequent worker is an
``os.fork()`` of the warm image (~10ms) — the same trick Android's zygote
and Ray's own prestart pool approximate.

Protocol: one unix-socket connection per fork request.  Request is a JSON
line ``{"env": {...}, "logfile": path}``; reply is ``{"pid": N}``.  The
forked child detaches (setsid), redirects stdio to its logfile, applies the
env, and runs the normal worker entry (worker_main.main()).  The zygote
reaps its children on SIGCHLD so kill(pid, 0) liveness probes see clean
deaths.
"""

from __future__ import annotations

import asyncio
import json
import os
import signal
import socket
import sys


# --------------------------------------------------------------- server side

def _reap(signum, frame):
    try:
        while True:
            pid, _ = os.waitpid(-1, os.WNOHANG)
            if pid == 0:
                break
    except ChildProcessError:
        pass


def _child_exec(conn: socket.socket, srv: socket.socket, req: dict):
    """Runs in the forked child; never returns."""
    try:
        signal.signal(signal.SIGCHLD, signal.SIG_DFL)
        os.setsid()
        conn.close()
        srv.close()
        logfile = req.get("logfile")
        if logfile:
            os.makedirs(os.path.dirname(logfile), exist_ok=True)
            fd = os.open(logfile, os.O_APPEND | os.O_CREAT | os.O_WRONLY,
                         0o644)
            os.dup2(fd, 1)
            os.dup2(fd, 2)
            os.close(fd)
        os.environ.update(req.get("env") or {})
        import random
        random.seed()  # forked children must not share the parent's stream
        from ray_tpu._private import worker_main
        worker_main.main()
    except BaseException:
        import traceback
        traceback.print_exc()
    finally:
        os._exit(0)


def main():
    sock_path = sys.argv[1]
    try:
        # Die with the raylet that spawned us (PR_SET_PDEATHSIG) — a
        # SIGKILLed raylet must not leave a warm fork-server behind.  The
        # flag is cleared in forked children, so workers are unaffected
        # (they exit when their raylet socket closes).
        import ctypes
        ctypes.CDLL("libc.so.6", use_errno=True).prctl(
            1, signal.SIGKILL, 0, 0, 0)  # PR_SET_PDEATHSIG = 1
    except Exception:
        pass
    signal.signal(signal.SIGCHLD, _reap)
    # Preload the worker stack while we're still single-purpose: every
    # import done here is an import no forked worker pays again.
    import ray_tpu._private.worker  # noqa: F401
    import ray_tpu._private.worker_main  # noqa: F401
    import ray_tpu.actor  # noqa: F401
    try:
        os.unlink(sock_path)
    except OSError:
        pass
    srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    srv.bind(sock_path)
    srv.listen(128)
    print("ZYGOTE_READY", flush=True)
    while True:
        try:
            conn, _ = srv.accept()
        except OSError:
            break
        try:
            buf = b""
            while not buf.endswith(b"\n"):
                chunk = conn.recv(65536)
                if not chunk:
                    break
                buf += chunk
            if not buf:
                continue
            req = json.loads(buf)
            sys.stdout.flush()
            sys.stderr.flush()
            pid = os.fork()
            if pid == 0:
                _child_exec(conn, srv, req)  # never returns
            conn.sendall(json.dumps({"pid": pid}).encode() + b"\n")
        except Exception:
            import traceback
            traceback.print_exc()
        finally:
            try:
                conn.close()
            except OSError:
                pass


# --------------------------------------------------------------- client side

class ZygoteClient:
    """Raylet-side handle to the zygote process."""

    def __init__(self, sock_path: str, proc):
        self.sock_path = sock_path
        self.proc = proc
        self.ready = False

    async def wait_ready(self, timeout: float = 120.0):
        """Wait for the zygote to finish preloading (its READY line)."""
        loop = asyncio.get_running_loop()
        deadline = loop.time() + timeout
        while loop.time() < deadline:
            if self.proc.poll() is not None:
                return False
            if os.path.exists(self.sock_path):
                try:
                    r, w = await asyncio.wait_for(
                        asyncio.open_unix_connection(self.sock_path), 5)
                    w.close()
                    self.ready = True
                    return True
                except OSError:
                    pass
            await asyncio.sleep(0.05)
        return False

    async def fork(self, env: dict, logfile: str,
                   timeout: float = 10.0) -> int:
        reader, writer = await asyncio.wait_for(
            asyncio.open_unix_connection(self.sock_path), timeout)
        try:
            writer.write(json.dumps({"env": env, "logfile": logfile})
                         .encode() + b"\n")
            await writer.drain()
            line = await asyncio.wait_for(reader.readline(), timeout)
            reply = json.loads(line)
            return reply["pid"]
        finally:
            writer.close()

    def kill(self):
        if self.proc is not None and self.proc.poll() is None:
            try:
                self.proc.kill()
            except OSError:
                pass
        try:
            os.unlink(self.sock_path)
        except OSError:
            pass


class PidHandle:
    """Popen-compatible shim for a fork-spawned worker (the zygote is its
    parent, so the raylet probes liveness with kill(pid, 0) instead of
    waitpid)."""

    def __init__(self, pid: int):
        self.pid = pid
        self.returncode = None

    def poll(self):
        if self.returncode is not None:
            return self.returncode
        try:
            os.kill(self.pid, 0)
            return None
        except (ProcessLookupError, PermissionError):
            self.returncode = -1
            return self.returncode

    def terminate(self):
        try:
            os.kill(self.pid, signal.SIGTERM)
        except (ProcessLookupError, PermissionError):
            pass

    def kill(self):
        try:
            os.kill(self.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass


if __name__ == "__main__":
    main()
