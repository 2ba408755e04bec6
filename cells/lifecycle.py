"""A run owns its chips from start to finish.

Before ``ray_tpu.init()``: count the chips, wait until no live process
holds one.  After the run, on success and on every error path: shut down,
wait until no process of the session is alive, kill what is left.  On any
failure: print the exception and the tail of every log of the session.
The pattern is ``chip_smoke.py``'s ``Smoke.start/stop`` and
``_session_pids`` (copied: the benchmark may not import a root script).
Every limit here is sized for the cold first run of a set on a fresh
checkout, several times what PR 21 measured cold (PERF.md section 5).
"""

import glob
import os
import shutil
import signal
import sys
import time
import traceback

# a worker reached its chip in 10-17 s cold (PR 21); a run that follows
# another starts the moment the last one exits
CHIPS_FREE_LIMIT_S = 120.0
SESSION_EXIT_LIMIT_S = 60.0
LOG_TAIL_LINES = 50


def chip_nodes():
    """Device nodes of the TPU chips of this host (``/dev/accel<n>`` or
    ``/dev/vfio/<n>``), as the program's own detection counts them."""
    nodes = glob.glob("/dev/accel[0-9]*")
    if not nodes:
        nodes = [p for p in glob.glob("/dev/vfio/*")
                 if os.path.basename(p).isdigit()]
    return sorted(nodes)


def chip_holders(nodes):
    """{device node: [pids]} for the chip nodes some live process other
    than this one has open."""
    want, held = set(nodes), {}
    for pid in filter(str.isdigit, os.listdir("/proc")):
        if int(pid) == os.getpid():
            continue
        try:
            fds = os.listdir(f"/proc/{pid}/fd")
        except OSError:
            continue
        for fd in fds:
            try:
                target = os.readlink(f"/proc/{pid}/fd/{fd}")
            except OSError:
                continue
            if target in want:
                held.setdefault(target, []).append(int(pid))
    return held


def wait_chips_free(nodes, limit_s=CHIPS_FREE_LIMIT_S):
    """Seconds waited.  Raises if a holder outlives the limit."""
    t0 = time.monotonic()
    while holders := chip_holders(nodes):
        if time.monotonic() - t0 > limit_s:
            raise RuntimeError(
                f"chips still held after {limit_s:.0f}s by {holders}")
        time.sleep(0.5)
    return time.monotonic() - t0


def session_pids(session_dir: str):
    """Live processes started for one session: the head names the session
    directory on its command line; the zygote, and every worker forked
    from it, in its environment."""
    needle, pids = session_dir.encode(), []
    for entry in os.listdir("/proc"):
        if not entry.isdigit() or int(entry) == os.getpid():
            continue
        try:
            with open(f"/proc/{entry}/cmdline", "rb") as f:
                blob = f.read()
            with open(f"/proc/{entry}/environ", "rb") as f:
                blob += f.read()
            with open(f"/proc/{entry}/stat", "rb") as f:
                zombie = f.read().rsplit(b")", 1)[1].split()[0] == b"Z"
        except OSError:
            continue
        if needle in blob and not zombie:
            pids.append(int(entry))
    return pids


def print_log_tails(session_dir: str, out=sys.stderr):
    """The last lines of every log of the session, to stderr."""
    logs = sorted(glob.glob(os.path.join(session_dir, "logs", "*")))
    print(f"---- {len(logs)} log(s) under {session_dir}/logs", file=out)
    for path in logs:
        try:
            with open(path, "rb") as f:
                f.seek(0, os.SEEK_END)
                f.seek(max(0, f.tell() - 16384))
                lines = f.read().decode("utf-8", "replace").splitlines()
        except OSError as e:
            lines = [f"(unreadable: {e})"]
        print(f"---- tail of {os.path.basename(path)}", file=out)
        for line in lines[-LOG_TAIL_LINES:]:
            print("  " + line, file=out)
    out.flush()


class Cluster:
    """``init()`` ... ``shutdown()`` with the guarantees above."""

    def __init__(self, chips: int, rehearse: bool):
        self.chips, self.rehearse = chips, rehearse
        self.session_dir = None
        self.waited_s = 0.0
        self.init_wall = self.started_wall = None
        self.serve_started = False

    def start(self):
        import ray_tpu

        if self.rehearse:
            self.init_wall = time.time()
            ray_tpu.init(num_cpus=8, log_to_driver=False)
        else:
            nodes = chip_nodes()
            if len(nodes) < self.chips:
                raise SystemExit(
                    f"cells: {len(nodes)} TPU chip(s) on this host, the "
                    f"cell needs {self.chips}: no result (--rehearse runs "
                    f"toy shapes on the CPU)")
            self.waited_s = wait_chips_free(nodes)
            self.init_wall = time.time()
            # the TPU resource comes from detection; the workers' output stays
            # in the session's logs, whose tails a failed run prints
            ray_tpu.init(log_to_driver=False)
        self.started_wall = time.time()
        self.session_dir = ray_tpu._node_services.session_dir
        if not self.rehearse:
            found = ray_tpu.cluster_resources().get("TPU", 0)
            if found < self.chips:
                raise SystemExit(
                    f"cells: ray_tpu detected {found:g} TPU chip(s), the "
                    f"cell needs {self.chips}: no result")

    def explain(self, exc=None):
        """What a failed run leaves for its reader."""
        if exc is not None:
            traceback.print_exception(type(exc), exc, exc.__traceback__,
                                      file=sys.stderr)
        if self.session_dir:
            print_log_tails(self.session_dir)

    def stop(self):
        """Never raises.  Returns the pids it had to kill."""
        if self.session_dir is None:
            return []
        killed = []
        try:
            import ray_tpu

            if self.serve_started:
                from ray_tpu import serve

                try:
                    serve.shutdown()
                except Exception:  # noqa: BLE001 - teardown goes on
                    traceback.print_exc()
            try:
                ray_tpu.shutdown()
            except Exception:  # noqa: BLE001
                traceback.print_exc()
            deadline = time.monotonic() + SESSION_EXIT_LIMIT_S
            while (pids := session_pids(self.session_dir)) and \
                    time.monotonic() < deadline:
                time.sleep(0.2)
            for pid in pids:
                try:
                    os.kill(pid, signal.SIGKILL)
                    killed.append(pid)
                except OSError:
                    pass
            if killed:
                print(f"cells: killed leftover session processes {killed}",
                      file=sys.stderr)
                time.sleep(0.5)
            shutil.rmtree(self.session_dir, ignore_errors=True)
        finally:
            self.session_dir = None
        return killed
