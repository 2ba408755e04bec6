"""Look at a trace by hand: planes, lines, the commonest event names.

    python cells/tools/dump_trace.py <file.xplane.pb> [mini.json.gz]

A ``--trace 1`` run leaves its file under ``.cells_work/trace/<cell>/``
until the cell's next run.

With a second argument it also writes the miniature the tests reduce
(device lines whole, host events over 0.2 ms) cut to the first second.
"""

import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from jax.profiler import ProfileData  # noqa: E402

from cells import trace  # noqa: E402


def main():
    path = sys.argv[1]
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            total = sum(e.duration_ns for e in events)
            names = collections.Counter()
            for e in events:
                names[e.name] += e.duration_ns
            print(f"  line {line.name!r}: {len(events)} events, "
                  f"{total / 1e6:.1f} ms")
            for name, ns in names.most_common(12):
                print(f"      {ns / 1e6:10.3f} ms  {name[:100]}")
            first = events[0]
            stats = [(k, str(v)[:60]) for k, v in first.stats][:12]
            print(f"      first event stats: {stats}")
    if len(sys.argv) > 2:
        tr = trace.load(path)
        t0, t1 = trace.span(tr)
        cut = min(t1, t0 + int(float(os.environ.get("MINI_S", "1.0")) * 1e9))
        trace.save_mini(tr, sys.argv[2], t0, cut)
        print(f"miniature: {sys.argv[2]} "
              f"{os.path.getsize(sys.argv[2])} bytes")


if __name__ == "__main__":
    main()
