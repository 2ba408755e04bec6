"""Look at a trace's device time by the programs' own name scopes: the
(program x part) table in seconds and per execution, and under each part
its largest instruction families with their ``op_name`` and result shape.

    python cells/tools/dump_parts.py [file.xplane.pb] [mini.json.gz]

Without a file it takes the newest under ``.cells_work/trace/`` (a
``--trace 1`` run leaves its file there until the cell's next run).  With a
second argument it also writes the miniature ``cells/tests/test_parts.py``
reduces: every device event with its ``op_name`` and every span with its
stats, cut to ``MINI_S`` (default 1.0) seconds from ``MINI_FROM_S``
(default 0) seconds after the first ``engine.step`` begins (after the
first device operation where the trace has no such span: a train run).

What it answers without a scratch compile: which program and which part of
the model a ``fusion`` is (ROADMAP: "read an instruction's ``op_name`` ...
before believing its name").
"""

import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from cells import parts, spans, trace  # noqa: E402

TOP = 5


def _result(name: str) -> str:
    """``%fusion.3 = bf16[32,4096]{1,0:T(8,128)} fusion(...)`` ->
    ``bf16[32,4096]``; a tuple's first element."""
    m = re.search(r"= \(?(\w+\[[\d,]*\])", name)
    return m.group(1) if m else ""


def main():
    path = sys.argv[1] if len(sys.argv) > 1 else spans.newest_xplane()
    if path is None:
        raise SystemExit(f"no .xplane.pb under {spans.TRACES}")
    t = time.time()
    loaded = parts.load(path)
    took = time.time() - t
    rows, runs = parts.attributed(loaded)
    named = sum(1 for e in loaded["ops"] if e[3])
    print(f"{path}: {len(loaded['ops'])} device events, {named} with an "
          f"op_name, {len(loaded['modules'])} executions; read in "
          f"{took:.1f} s")
    if not loaded["ops"]:
        return
    first = min(e[1] for e in loaded["ops"])
    last = max(e[1] + e[2] for e in loaded["ops"])
    busy = trace.length(trace.merge(
        (e[1], e[1] + e[2]) for e in loaded["ops"]))
    print(f"window {(last - first) / 1e9:.3f} s, busy {busy / 1e9:.3f} s")

    count = {}
    for kind, _, dur in runs:
        n, total = count.get(kind, (0, 0))
        count[kind] = (n + 1, total + dur)
    table, families = {}, {}
    for program, part, replay, self_ns, name, op_name in rows:
        table[(program, part)] = table.get((program, part), 0) + self_ns
        fam = re.sub(r"[.\d]+$", "", trace.short(name)) or trace.short(name)
        key = (program, part, fam + (" [replay]" if replay else ""))
        acc = families.setdefault(key, [0, 0, name, op_name])
        acc[0] += self_ns
        acc[1] += 1
    for program in sorted({p for p, _ in table}, key=str):
        n, total = count.get(program, (0, 0))
        secs = sum(v for (p, _), v in table.items() if p == program) / 1e9
        print(f"\n{program or '(programs without a scope)'}: {n} "
              f"executions, {total / 1e9:.4f} s of device time, self times "
              f"{secs:.4f} s = {100 * secs * 1e9 / (last - first):.2f}% of "
              f"the window")
        for (p, part), ns in sorted(table.items(), key=lambda kv: -kv[1]):
            if p != program:
                continue
            print(f"  {part:16s} {ns / 1e9:9.4f} s  "
                  f"{ns / 1e6 / max(n, 1):9.4f} ms an execution  "
                  f"{100 * ns / max(secs * 1e9, 1):6.2f}%")
            fams = sorted(((k[2], v) for k, v in families.items()
                           if k[:2] == (p, part)), key=lambda kv: -kv[1][0])
            for fam, (fns, calls, name, op_name) in fams[:TOP]:
                print(f"      {fns / 1e9:9.4f} s {calls:7d} x {fam:40s} "
                      f"{_result(name):24s} {op_name or ''}")
    if len(sys.argv) > 2:
        threads = spans.load(path)
        events = spans.engine_thread(threads)
        t0 = min((e[1] for e in events or [] if e[0] == spans.STEP),
                 default=min(first, loaded["modules"][0][1]))
        t0 += int(float(os.environ.get("MINI_FROM_S", "0")) * 1e9)
        cut_s = float(os.environ.get("MINI_S", "1.0"))
        parts.save_mini(loaded, threads, sys.argv[2], t0,
                        t0 + int(cut_s * 1e9))
        print(f"miniature: {sys.argv[2]} "
              f"{os.path.getsize(sys.argv[2])} bytes")


if __name__ == "__main__":
    main()
