"""Where set-up went, and which programs were built inside a trace: the
readers of the program's build ledger and start-up spans.

``ray_tpu._private.tracing.watch_builds`` counts every program a process
traces, lowers, builds or loads from the compile cache, and with tracing on
writes each backend compile into a profiler's trace as one instant
``xla.build`` at its end, on the thread that built it (stats ``program``,
``ms``, ``cached``): the build is ``[end - ms, end]``.  The serve loop's
``serve.publish_stats`` (every 2 s on the engine thread) carries the
replica's ledger and its engine's set-up parts as stats, so the last one in
a trace is the replica's set-up, for a reader that holds the trace alone.
The driver's ``ray_tpu.init()`` is an ``init`` span in the driving
process's own buffer, which outlives ``shutdown()``.

``spans.load`` keeps ``engine.*`` / ``serve.*`` names only, so the
``xla.build`` instants are read from the same file here (``of_run`` and
``named`` serve for the rest).  Against a program without the ledger (a
commit before it) every reader returns ``None``: no snapshot on the span,
no ``init`` in the buffer, and a count of builds would be a zero that nobody
counted.
"""

from cells import spans, trace

BUILD = "xla.build"
SNAPSHOT = "serve.publish_stats"


def has_ledger() -> bool:
    """Does the program under test write ``xla.build`` at all?"""
    from ray_tpu._private import tracing

    return hasattr(tracing, "watch_builds")


def load_builds(path: str) -> list:
    """[(end_ns, ms, program, cached, thread), ...] by end: the
    ``xla.build`` instants of every host thread in an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name == BUILD:
                    st = dict(e.stats)
                    out.append((int(e.start_ns), float(st.get("ms", 0.0)),
                                str(st.get("program", "")),
                                int(st.get("cached", 0)),
                                f"{line.name}#{i}"))
    return sorted(out)


def builds_of_run(ctx):
    """The builds inside the traced window (first to last device
    operation) of the run whose readers share ``ctx``; ``None`` where the
    trace has no spans to set them against, or the program no ledger."""
    if not spans.of_run(ctx) or not has_ledger():
        return None
    if "builds" not in ctx:
        path = spans.newest_xplane()
        ctx["builds"] = load_builds(path) if path else []
    first, last = trace.span(ctx["trace"])
    return [b for b in ctx["builds"] if first <= b[0] <= last]


def snapshot(ctx):
    """The stats of the trace's last ``serve.publish_stats`` that carries
    the ledger; ``None`` without one."""
    carried = [e[3] for e in spans.named(spans.of_run(ctx) or {}, SNAPSHOT)
               if "built" in e[3]]
    return carried[-1] if carried else None


# ------------------------------------------------------------ the readers

def snapshot_stat(ctx, stat):
    snap = snapshot(ctx)
    return None if snap is None or stat not in snap else float(snap[stat])


def setup_programs_s(ctx):
    """Seconds the replica spent tracing, lowering, building or loading
    programs since it started."""
    snap = snapshot(ctx)
    if snap is None:
        return None
    return (snap["build_ms"] + snap["load_ms"] + snap["lower_ms"]) / 1e3


def builds_in_trace(ctx):
    builds = builds_of_run(ctx)
    return None if builds is None else float(len(builds))


def build_ms_in_trace(ctx):
    builds = builds_of_run(ctx)
    return None if builds is None else float(sum(b[1] for b in builds))


def idle_build_pct(ctx):
    """Idle time of chip 0 that overlaps a build on any thread, in percent
    of the traced window: a part of the five ``idle_*`` groups (whichever
    phase the engine thread was in), not a sixth addend."""
    builds = builds_of_run(ctx)
    if builds is None or not ctx.get("trace_window_s"):
        return None
    building = trace.merge((end - int(ms * 1e6), end)
                           for end, ms, *_ in builds)
    idle = spans.idle_intervals(ctx["trace"])
    return 100.0 * spans.overlap(idle, building) / 1e9 / ctx["trace_window_s"]


def init_s(ctx=None):
    """Seconds of the driving process's last ``ray_tpu.init()``, from its
    own ``init`` span."""
    from ray_tpu._private import tracing

    inits = [s for s in tracing.local_spans(include_open=False)
             if s["name"] == "init"]
    return inits[-1]["end"] - inits[-1]["start"] if inits else None
