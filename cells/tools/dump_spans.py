"""Look at the program's spans in a trace by hand: the engine thread's
phases window by window, the device's idle time under each group of them,
and the request threads' waits for the lock.

    python cells/tools/dump_spans.py [file.xplane.pb] [mini.json.gz]

Without a file it takes the newest under ``.cells_work/trace/`` (a
``--trace 1`` run leaves its file there until the cell's next run).  With a
second argument it also writes the miniature ``cells/tests/test_spans.py``
reduces: the device lines and every span with its stats, cut to the first
``MINI_S`` (default 2.2) seconds after the first ``engine.step`` begins.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from cells import spans, trace  # noqa: E402


def frames_under(tr, threads, rest, top=6, floor_ms=1.0):
    """Which thread ran what in ``rest`` (the idle time no phase
    explains): per host thread of the trace, the innermost events (the
    Python tracer's frames among them) by their overlap with it."""
    import numpy as np

    if not rest:
        return
    engine = spans.engine_thread(threads)
    mine = next((k for k, ev in threads.items() if ev is engine), None)
    print(f"    in those {trace.length(rest) / 1e6:.3f} ms, by thread "
          f"(the engine thread is {mine}):")
    # ns of ``rest`` before an instant: its overlap with [s, e) is the
    # difference of two readings (a trace holds millions of frames)
    edges = np.array(rest, np.float64)  # [[start, end], ...], merged
    done = np.cumsum(edges[:, 1] - edges[:, 0])
    before = np.stack([done - (edges[:, 1] - edges[:, 0]), done], 1).ravel()
    edges = edges.ravel()

    def under(t):
        return np.interp(t, edges, before)

    lo, hi = rest[0][0], rest[-1][1]
    for key, events in tr["host"].items():
        cut = spans.pieces([(n, s, d, None) for n, s, d in events
                            if s < hi and s + d > lo])
        if not cut:
            continue
        ns = under(np.array([e for _, e, _ in cut], np.float64)) \
            - under(np.array([s for s, _, _ in cut], np.float64))
        acc = {}
        for i in np.nonzero(ns > 0)[0]:
            acc[cut[i][2]] = acc.get(cut[i][2], 0) + ns[i]
        if sum(acc.values()) / 1e6 < floor_ms:
            continue
        rows = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
        print(f"      {key}: " + "; ".join(
            f"{n[:60]} {v / 1e6:.2f}" for n, v in rows))


def main():
    path = sys.argv[1] if len(sys.argv) > 1 else spans.newest_xplane()
    if path is None:
        raise SystemExit(f"no .xplane.pb under {spans.TRACES}")
    tr = trace.load(path)
    threads = spans.load(path)
    print(f"{path}: {len(threads)} thread(s) with spans")
    events = spans.engine_thread(threads)
    if events is None:
        print("no engine.step in this trace")
        return
    t0, t1 = trace.span(tr) if tr["device"] else (events[0][1], 0)
    if tr["device"]:
        idle = spans.idle_intervals(tr)
        by_group = spans.idle_ns_by_group(tr, threads)
        print(f"device window {(t1 - t0) / 1e6:.1f} ms, idle "
              f"{trace.length(idle) / 1e6:.2f} ms:")
        for group, ns in by_group.items():
            print(f"    {group:16s} {ns / 1e6:9.3f} ms  "
                  f"{100 * ns / (t1 - t0):6.3f} %")
        # the unattributed idle time, by the piece of the engine thread
        # (or the lack of one) it lies under
        cut = spans.pieces(events)
        step = trace.merge((s, e) for s, e, n in cut if n == spans.STEP)
        covered = trace.merge((s, e) for s, e, _ in cut)
        print(f"    of the unattributed: under engine.step's own time "
              f"{spans.overlap(idle, step) / 1e6:.3f} ms, under no span "
              f"{trace.length(trace.subtract(idle, covered)) / 1e6:.3f} ms")
        frames_under(tr, threads, trace.subtract(
            idle, trace.subtract(covered, step)))
    print("the engine thread, ms from the first device operation "
          "(start, duration, name, stats):")
    for name, start, dur, stats in sorted(events, key=lambda e: e[1]):
        depth = 0 if name.startswith("serve.") or name == spans.STEP else 1
        print(f"  {(start - t0) / 1e6:10.3f} {dur / 1e6:9.3f}  "
              f"{'  ' * depth}{name} {stats or ''}")
    waits = spans.named(threads, "serve.lock_wait", who="submit")
    print(f"{len(waits)} serve.lock_wait on request threads, ms: "
          f"{[round(e[2] / 1e6, 1) for e in waits]}")
    if len(sys.argv) > 2:
        first = min(e[1] for e in events if e[0] == spans.STEP)
        cut_s = float(os.environ.get("MINI_S", "2.2"))
        spans.save_mini(tr, threads, sys.argv[2], first,
                        first + int(cut_s * 1e9))
        print(f"miniature: {sys.argv[2]} "
              f"{os.path.getsize(sys.argv[2])} bytes")


if __name__ == "__main__":
    main()
