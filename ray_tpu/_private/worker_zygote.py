"""Worker zygote: fork-based fast worker spawning.

TPU-native equivalent of the reference WorkerPool's prestart capability
(``src/ray/raylet/worker_pool.h`` — PrestartWorkers / PopWorker hide
process-start latency).  The reference prestarts whole idle python
processes; here ONE zygote process pays the interpreter + heavy-import
cost (jax alone is most of it), then every worker is an ``os.fork()``
away — milliseconds instead of seconds a worker, which is what starting
1,000 actors on one host turns on.

Fork safety: the zygote imports modules but never initializes a jax
backend, starts an event loop, or spawns threads — children initialize
everything post-fork.  Children call ``os.setsid()`` (own session, like
the Popen path's ``start_new_session``) and are reaped by the zygote's
accept loop (they are the zygote's children, not the raylet's; the
raylet probes liveness by pid as it already does for re-adopted
workers).

Protocol (length-prefixed JSON over the zygote's unix socket):
  request:  {"env": {...}, "log_path": "..."}  -> fork a worker
  reply:    {"pid": <child pid>}
A connection error or malformed request is answered with best effort and
never kills the zygote; the raylet falls back to the Popen spawn path if
the zygote is unavailable.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import struct
import sys


def _preload() -> None:
    """Import the heavy modules once, pre-fork.  Anything imported here
    is shared COW by every worker.  Backend-initializing calls (e.g.
    ``jax.devices()``) are deliberately absent: they create threads and
    claim accelerators, both fork-hostile."""
    import ray_tpu  # noqa: F401
    import ray_tpu._private.worker  # noqa: F401
    import ray_tpu._private.worker_proc  # noqa: F401

    try:
        import jax  # noqa: F401  (the ~1s+ import is the whole point)
        import jax.numpy  # noqa: F401
    except Exception:  # noqa: BLE001 - jax-less environments still work
        pass
    try:
        import numpy  # noqa: F401
    except Exception:  # noqa: BLE001
        pass


def _recv_msg(conn: socket.socket) -> dict:
    hdr = b""
    while len(hdr) < 4:
        chunk = conn.recv(4 - len(hdr))
        if not chunk:
            raise ConnectionError("zygote request truncated")
        hdr += chunk
    (n,) = struct.unpack("<I", hdr)
    if n > 1 << 20:
        raise ValueError(f"zygote request too large: {n}")
    data = b""
    while len(data) < n:
        chunk = conn.recv(n - len(data))
        if not chunk:
            raise ConnectionError("zygote request truncated")
        data += chunk
    return json.loads(data)


def _send_msg(conn: socket.socket, obj: dict) -> None:
    data = json.dumps(obj).encode()
    conn.sendall(struct.pack("<I", len(data)) + data)


def _reap() -> None:
    try:
        while True:
            pid, _ = os.waitpid(-1, os.WNOHANG)
            if pid == 0:
                break
    except ChildProcessError:
        pass


def proc_starttime(pid: int):
    """Kernel start time (clock ticks since boot) from /proc/<pid>/stat —
    a (pid, starttime) pair uniquely identifies a process incarnation, so
    liveness probes and kills can't hit a recycled pid.  None if gone."""
    try:
        with open(f"/proc/{pid}/stat", "rb") as f:
            data = f.read()
        # field 2 (comm) may contain spaces/parens; fields after the LAST
        # ')' are well-formed — starttime is the 20th of those
        return int(data.rsplit(b")", 1)[1].split()[19])
    except (OSError, IndexError, ValueError):
        return None


def _spawn(req: dict) -> int:
    env = req.get("env", {})
    log_path = req.get("log_path")
    pid = os.fork()
    if pid != 0:
        return pid
    # ---- child: becomes a worker process ----
    try:
        os.setsid()
        if log_path:
            fd = os.open(log_path, os.O_WRONLY | os.O_CREAT | os.O_APPEND,
                         0o644)
            os.dup2(fd, 1)
            os.dup2(fd, 2)
            if fd > 2:
                os.close(fd)
        os.environ.update({str(k): str(v) for k, v in env.items()})
        # default signal dispositions (the zygote ignores SIGINT)
        signal.signal(signal.SIGINT, signal.SIG_DFL)
        signal.signal(signal.SIGTERM, signal.SIG_DFL)
        from ray_tpu._private import worker_proc

        worker_proc.main()
    except BaseException:  # noqa: BLE001 - never return into the accept loop
        import traceback

        traceback.print_exc()
    finally:
        os._exit(0)
    return 0  # unreachable


def main() -> None:
    sock_path = os.environ["RAY_TPU_ZYGOTE_SOCK"]
    _preload()
    srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    try:
        os.unlink(sock_path)
    except FileNotFoundError:
        pass
    srv.bind(sock_path + ".tmp")
    srv.listen(64)
    # atomic publish: the raylet treats the socket's existence as "ready"
    os.rename(sock_path + ".tmp", sock_path)
    signal.signal(signal.SIGINT, signal.SIG_IGN)
    srv.settimeout(1.0)
    while True:
        _reap()
        try:
            conn, _ = srv.accept()
        except socket.timeout:
            continue
        except OSError:
            break
        try:
            req = _recv_msg(conn)
            if req.get("cmd") == "stop":
                _send_msg(conn, {"ok": True})
                break
            pid = _spawn(req)
            _send_msg(conn, {"pid": pid,
                             "starttime": proc_starttime(pid)})
        except Exception as e:  # noqa: BLE001 - one bad request, not fatal
            try:
                _send_msg(conn, {"error": str(e)})
            except Exception:  # noqa: BLE001
                pass
        finally:
            try:
                conn.close()
            except OSError:
                pass
    try:
        srv.close()
        os.unlink(sock_path)
    except OSError:
        pass


if __name__ == "__main__":
    main()
