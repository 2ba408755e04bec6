"""Where a replica's set-up went, and which programs were built inside a
traced window, from a run's ``.xplane.pb`` alone:

    python cells/tools/dump_startup.py [file.xplane.pb]

Without a file it takes the newest under ``.cells_work/trace/`` (a
``--trace 1`` run leaves its file there until the cell's next run).  Prints
the snapshot that the trace's last ``serve.publish_stats`` carries (the
process's build ledger and the engine's set-up parts), then every
``xla.build`` of the trace, largest first: its program under the name the
compile cache's files and ``cells: N program(s) compiled inside the
window`` give it, its milliseconds, whether the cache held it, the thread
that built it and the serve-loop phase that thread was in, and how much of
it the chip stood idle.  Builds before the profiler started are in the
snapshot's sums only; by program they are the ``xla.build`` spans of the
replica's host buffer (``ray_tpu.util.state.timeline()`` while the cluster
is up).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from cells import spans, startup, trace  # noqa: E402


def phase_at(events, at_ns):
    """The innermost ``engine.*`` / ``serve.*`` span of a thread that
    covers an instant; '-' where none does."""
    covering = [e for e in events or () if e[1] <= at_ns <= e[1] + e[2]]
    return min(covering, key=lambda e: e[2])[0] if covering else "-"


def main():
    path = sys.argv[1] if len(sys.argv) > 1 else spans.newest_xplane()
    if path is None:
        raise SystemExit(f"no .xplane.pb under {spans.TRACES}")
    tr = trace.load(path)
    threads = spans.load(path)
    builds = startup.load_builds(path)
    ctx = {"trace": tr, "spans": threads}
    print(path)
    snap = startup.snapshot(ctx)
    if snap is None:
        print("no serve.publish_stats with the build ledger in this trace")
    else:
        print("the replica since its start (last serve.publish_stats):")
        total = snap["startup_total_s"]
        rest = total - sum(snap[f"startup_{p}_s"]
                           for p in ("backend", "weights", "pool"))
        print(f"    engine.startup {total:.3f} s = backend "
              f"{snap['startup_backend_s']:.3f} + weights "
              f"{snap['startup_weights_s']:.3f} + pool "
              f"{snap['startup_pool_s']:.3f} + the rest {rest:.3f}")
        programs = (snap["build_ms"] + snap["load_ms"]
                    + snap["lower_ms"]) / 1e3
        print(f"    programs {programs:.3f} s = traced and lowered "
              f"{snap['lower_ms'] / 1e3:.3f} + {snap['built']} built "
              f"{snap['build_ms'] / 1e3:.3f} + {snap['loaded']} loaded "
              f"from the cache {snap['load_ms'] / 1e3:.3f}")
    first, last = trace.span(tr) if tr["device"] else (0, 0)
    idle = spans.idle_intervals(tr) if tr["device"] else []
    inside = [b for b in builds if first <= b[0] <= last]
    print(f"{len(builds)} xla.build in the trace, {len(inside)} between "
          f"the first and the last device operation, "
          f"{sum(b[1] for b in inside):.3f} ms:")
    print(f"    {'ms':>10} {'idle ms':>9}  cached  {'at ms':>9}  "
          f"program, thread, phase")
    for end, ms, program, cached, thread in sorted(
            builds, key=lambda b: -b[1]):
        span = [(end - int(ms * 1e6), end)]
        print(f"    {ms:10.3f} {spans.overlap(idle, span) / 1e6:9.3f}  "
              f"{cached:6d}  {(end - first) / 1e6:9.1f}  {program}, "
              f"{thread}, {phase_at(threads.get(thread), end)}")
    by_program = {}
    for _, ms, program, *_ in builds:
        n, total = by_program.get(program, (0, 0.0))
        by_program[program] = (n + 1, total + ms)
    for program, (n, total) in sorted(by_program.items(),
                                      key=lambda kv: -kv[1][1]):
        print(f"    {program}: {n} build(s), {total:.3f} ms")


if __name__ == "__main__":
    main()
