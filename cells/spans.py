"""The program's own spans in a profiler trace, and the device's idle time
under each of them.

``ray_tpu._private.tracing.annotate`` writes the serve loop's phases
(``serve.*`` around ``LLMEngine.step``, ``engine.*`` inside it) into the
profiler's trace as ``TraceAnnotation`` events: plain names on the line of
the thread that ran them, in the plane ``/host:CPU``, on the clock of the
device's ``XLA Ops``, with their keyword arguments as the event's stats.
``trace.load`` keeps names only, so this module reads the file again, keeps
the events whose names start with ``engine.`` or ``serve.`` with their
stats, and offers the readers in ``layer_metrics/``:

* the engine thread (the line that holds ``engine.step``) cut into disjoint
  pieces, each labelled by the innermost span that covers it (a span's
  *self* time);
* the idle intervals of chip 0 (the complement of the merged ``XLA Ops``
  between the first and the last operation: the window ``device_idle_pct``
  uses) and how much of them lies under the pieces of each group of spans;
* the spans of a name, from every thread, with their stats.

``run.py`` hands a reader no path, so ``of_run`` takes the newest
``.xplane.pb`` under ``.cells_work/trace/`` (a run empties its cell's
directory first) and keeps what it read in the readers' shared ``ctx``.
Where the program writes no such span (a commit before the spans, a
rehearsal without a device plane) every function returns ``None``.
"""

import glob
import gzip
import json
import os
import statistics

from cells import trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACES = os.path.join(ROOT, ".cells_work", "trace")
PREFIXES = ("engine.", "serve.")
STEP = "engine.step"

# the groups the five ``idle_*_pct.steady`` metrics put idle time under;
# ``engine.step``'s own self time and whatever no span covers are the rest
GROUPS = {
    "host_prepare": ("engine.admit", "engine.prepare_window",
                     "engine.dispatch_window", "engine.verify"),
    "sync": ("engine.first_tokens", "engine.fetch_window"),
    "emit": ("engine.emit", "engine.retire", "serve.deliver"),
    "between_steps": ("serve.lock_wait", "serve.publish_stats",
                      "serve.settle", "serve.idle"),
}


def newest_xplane():
    files = glob.glob(os.path.join(TRACES, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def load(path: str) -> dict:
    """{thread: [(name, start_ns, dur_ns, {stat: value}), ...]} of the
    ``engine.*``/``serve.*`` events, from an ``.xplane.pb`` or from the
    miniature ``.json.gz`` that ``save_mini`` wrote."""
    if path.endswith(".json.gz"):
        with gzip.open(path, "rt") as f:
            raw = json.load(f)
        return {k: [(n, s, d, st) for n, s, d, st in v]
                for k, v in raw.get("spans", {}).items()}
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for i, line in enumerate(plane.lines):  # as trace.load numbers them
            events = [(e.name, int(e.start_ns), int(e.duration_ns),
                       dict(e.stats))
                      for e in line.events if e.name.startswith(PREFIXES)]
            if events:
                out[f"{line.name}#{i}"] = events
    return out


def save_mini(tr: dict, threads: dict, path: str, t0_ns: int, t1_ns: int):
    """Cut a loaded trace and its spans to [t0, t1): every device event
    with its times (its name cut to 48 characters: an instruction's whole
    text is not needed here), no other host event, every span with its
    stats.  ``trace.load`` reads the device part of the file, ``load`` the
    spans."""
    def cut(events, width=None):
        return [[e[0][:width], *e[1:]] for e in events
                if t0_ns <= e[1] < t1_ns]
    raw = {"device": {str(k): {ln: cut(ev, 48) for ln, ev in v.items()}
                      for k, v in tr["device"].items()},
           "host": {},
           "spans": {k: c for k, v in threads.items() if (c := cut(v))}}
    with gzip.open(path, "wt") as f:
        json.dump(raw, f, separators=(",", ":"))


def of_run(ctx):
    """The spans of the run whose readers share ``ctx``, or ``None``
    where there is no device plane to set them against."""
    if ctx.get("trace") is None:
        return None
    if "spans" not in ctx:
        path = newest_xplane()
        ctx["spans"] = load(path) if path else {}
    return ctx["spans"]


# ------------------------------------------------------------ the pieces

def engine_thread(threads):
    """Events of the line that holds ``engine.step``; ``None`` without."""
    return next((ev for ev in threads.values()
                 if any(e[0] == STEP for e in ev)), None)


def named(threads, name, **where):
    """Every span of that name (whose stats match ``where``), from every
    thread, by start."""
    return sorted((e for ev in threads.values() for e in ev
                   if e[0] == name
                   and all(e[3].get(k) == v for k, v in where.items())),
                  key=lambda e: e[1])


def pieces(events):
    """[(start, end, name)], disjoint and sorted: one thread's nested
    spans cut so that every instant belongs to the innermost span that
    covers it."""
    out, stack = [], []  # stack of [name, end]; cur = how far it is cut

    def emit(upto):
        nonlocal cur
        if stack and upto > cur:
            out.append((cur, upto, stack[-1][0]))
        cur = max(cur, upto)

    cur = 0
    for name, start, dur, _ in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            emit(stack[-1][1])
            stack.pop()
        emit(start)
        stack.append([name, start + dur])
    while stack:
        emit(stack[-1][1])
        stack.pop()
    return out


def idle_intervals(tr):
    """Merged idle intervals of the first chip inside its first-to-last
    operation."""
    chips = sorted(tr["device"])
    if not chips:
        return []
    busy = trace.merge((s, s + d) for _, s, d in trace.ops(tr, chips[0]))
    if not busy:
        return []
    return trace.subtract([(busy[0][0], busy[-1][1])], busy)


def overlap(a, b) -> int:
    """ns that merged ``a`` and merged ``b`` have in common."""
    return trace.length(a) - trace.length(trace.subtract(a, b))


def idle_ns_by_group(tr, threads):
    """{group: ns of chip 0's idle time under that group's self time, ...,
    "unattributed": the rest}; ``None`` without an engine thread."""
    events = engine_thread(threads)
    if events is None:
        return None
    idle = idle_intervals(tr)
    cut = pieces(events)
    out = {}
    for group, names in GROUPS.items():
        own = trace.merge((s, e) for s, e, n in cut if n in names)
        out[group] = overlap(idle, own)
    out["unattributed"] = trace.length(idle) - sum(out.values())
    return out


# ------------------------------------------------------------ the readers

def idle_share_pct(ctx, group):
    """Idle time of chip 0 under ``group``'s spans (or under none:
    ``unattributed``), in percent of the traced window."""
    threads = of_run(ctx)
    if not threads or not ctx.get("trace_window_s"):
        return None
    by_group = idle_ns_by_group(ctx["trace"], threads)
    if by_group is None:
        return None
    return 100.0 * by_group[group] / 1e9 / ctx["trace_window_s"]


def window_period_ms(ctx):
    """Median start-to-start of consecutive ``engine.dispatch_window``."""
    starts = [e[1] for e in named(of_run(ctx) or {},
                                  "engine.dispatch_window")]
    gaps = [b - a for a, b in zip(starts, starts[1:])]
    return statistics.median(gaps) / 1e6 if gaps else None


def window_host_ms(ctx):
    """Median of ``engine.fetch_window``'s end to the next
    ``engine.dispatch_window``'s start: the serial host section."""
    threads = of_run(ctx) or {}
    launches = [e[1] for e in named(threads, "engine.dispatch_window")]
    gaps = []
    for _, start, dur, _ in named(threads, "engine.fetch_window"):
        nxt = next((s for s in launches if s >= start + dur), None)
        if nxt is not None:
            gaps.append(nxt - start - dur)
    return statistics.median(gaps) / 1e6 if gaps else None


def mean_stat(ctx, name, stat, **where):
    """Mean of ``stat`` over the spans of ``name`` whose stats match."""
    values = [e[3][stat] for e in named(of_run(ctx) or {}, name, **where)
              if stat in e[3]]
    return float(statistics.fmean(values)) if values else None


def mean_duration_ms(ctx, name, **where):
    durs = [e[2] for e in named(of_run(ctx) or {}, name, **where)]
    return statistics.fmean(durs) / 1e6 if durs else None
