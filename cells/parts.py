"""Device time cut by the programs' own name scopes: which program, and
which part of the model, the busy chip was running.

``ray_tpu._private.tracing.scope`` puts two levels of name scope inside
the device programs: the program (``engine.decode``, ``engine.prefill``,
``engine.verify``, ``train.step``: where the engine and the trainer build
them) and the part (``attn.proj``, ``experts``, ``head``, ...: in the
models).  A scope is metadata of the compiled instructions: every
instruction's ``op_name`` reads
``jit(<unknown>)/engine.decode/attn.proj/dot_general``, with whatever JAX
adds around and between (``jvp(head)``, ``transpose(jvp())``,
``checkpoint/rematted_computation``).

**The join.**  The profiler's trace carries each instruction's ``op_name``
itself: every event of a device's ``XLA Ops`` line points at an *event
metadata* entry (one an instruction of a program, shared by all its
executions), and that entry holds the stat ``tf_op`` = ``<op_name>:``
beside ``hlo_category``, ``flops``, ``bytes_accessed``, ``source`` and
``shape_with_layout`` (looked at on the v5e, PR 37).
``jax.profiler.ProfileData`` shows an event's OWN stats only
(``device_offset_ps``, ``device_duration_ps``), so ``trace.load`` never
saw it; ``load`` here reads the ``.xplane.pb`` as the protobuf it is, with
a message class built from the few fields used (``google.protobuf`` alone,
no TensorFlow import).  Names and times come out as ``trace.load`` gives
them.  A warm compile cache can hand back an executable whose scopes are
stale (JAX's default cache key leaves metadata out):
``ray_tpu/_private/node.py:ensure_compile_cache_env`` takes it in.

One rule for what XLA made itself (a ``copy``, a ``.remat`` twin, a fusion
whose root carries no metadata): it belongs to the *program* of the
execution that encloses it (the program scope of most of that execution's
scoped self time) and to part ``(unscoped)``.  A fusion that spans two
parts goes to its root's: the fusion instruction carries its root's
``op_name``.

Times are self times on chip 0 as ``trace.self_times`` gives them (a
``while`` holds its body's).  Where the program has no scopes (a commit
before them, a rehearsal without a device plane) every reader returns
``None``.
"""

import gzip
import json
import re
import time

from cells import spans, trace

PROGRAMS = ("engine.decode", "engine.prefill", "engine.verify", "train.step")
PARTS = ("embed", "attn.proj", "attn.cache", "attn.core", "attn.out", "ffn",
         "router", "experts", "experts.combine", "head", "sample", "loss",
         "optimizer")
UNSCOPED = "(unscoped)"
REPLAY = "rematted_computation"
TF_OP = "tf_op"  # the stat of an instruction's event metadata: its op_name


def scopes_of(op_name):
    """(program, part) of an ``op_name``: the first word that is a program
    scope, the innermost that is a part; ``None`` where there is none."""
    words = re.split(r"[/();]", op_name or "")  # ``;`` joins two ops' names
    parts = [w for w in words if w in PARTS]
    return (next((w for w in words if w in PROGRAMS), None),
            parts[-1] if parts else None)


def is_replay(name, op_name):
    """A rematerialised instruction: traced under ``jax.checkpoint``'s
    replay, or one of the ``.remat`` twins XLA makes itself."""
    return REPLAY in (op_name or "") or ".remat" in trace.short(name)


# ------------------------------------------------------------ load, save

def _space():
    """The message class of an ``.xplane.pb`` (``XSpace``), built at run
    time from the few fields of ``xplane.proto`` this module reads."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)

    T = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(
        name="cells_parts.proto", package="cp", syntax="proto3")

    def msg(name, *fields):
        m = f.message_type.add(name=name)
        for fname, number, kind, repeated in fields:
            fd = m.field.add(
                name=fname, number=number,
                label=T.LABEL_REPEATED if repeated else T.LABEL_OPTIONAL,
                type=getattr(T, "TYPE_" + kind.upper(), T.TYPE_MESSAGE))
            if fd.type == T.TYPE_MESSAGE:
                fd.type_name = ".cp." + kind
        return m

    msg("Stat", ("metadata_id", 1, "int64", 0), ("str_value", 5, "string", 0),
        ("ref_value", 7, "uint64", 0))
    msg("StatMetadata", ("name", 2, "string", 0))
    msg("EventMetadata", ("name", 2, "string", 0), ("stats", 5, "Stat", 1))
    msg("Event", ("metadata_id", 1, "int64", 0), ("offset_ps", 2, "int64", 0),
        ("duration_ps", 3, "int64", 0))
    msg("Line", ("name", 2, "string", 0), ("timestamp_ns", 3, "int64", 0),
        ("events", 4, "Event", 1))
    plane = msg("Plane", ("name", 2, "string", 0), ("lines", 3, "Line", 1),
                ("event_metadata", 4, "Plane.EventEntry", 1),
                ("stat_metadata", 5, "Plane.StatEntry", 1))
    for entry, value in (("EventEntry", "EventMetadata"),
                         ("StatEntry", "StatMetadata")):
        e = plane.nested_type.add(name=entry)
        e.options.map_entry = True
        e.field.add(name="key", number=1, type=T.TYPE_INT64,
                    label=T.LABEL_OPTIONAL)
        e.field.add(name="value", number=2, type=T.TYPE_MESSAGE,
                    label=T.LABEL_OPTIONAL, type_name=".cp." + value)
    msg("Space", ("planes", 1, "Plane", 1))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("cp.Space"))


def load(path: str) -> dict:
    """{"ops": [(name, start_ns, dur_ns, op_name | None)], "modules":
    [(name, start_ns, dur_ns)]} of the first chip's ``XLA Ops`` and ``XLA
    Modules`` lines, names and times as ``trace.load`` gives them, from an
    ``.xplane.pb`` or from what ``save_mini`` kept of one."""
    if path.endswith(".json.gz"):
        with gzip.open(path, "rt") as f:
            raw = json.load(f)["parts"]
        return {"ops": [tuple(e) for e in raw["ops"]],
                "modules": [tuple(e) for e in raw["modules"]]}
    space = _space()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    chips = sorted((int(m.group(1)), p) for p in space.planes
                   if (m := re.match(r"^/device:TPU:(\d+)$", p.name)))
    out = {"ops": [], "modules": []}
    if not chips:
        return out
    plane = chips[0][1]
    stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
    tf_op = next((k for k, n in stat_names.items() if n == TF_OP), None)
    meta = {}  # metadata id -> (the instruction's text, its op_name)
    for mid, m in plane.event_metadata.items():
        value = next((s.str_value or stat_names.get(s.ref_value, "")
                      for s in m.stats if s.metadata_id == tf_op), "")
        # ``<op_name>:<op_type>``, the type empty in every trace seen
        meta[mid] = (m.name, value.rsplit(":", 1)[0] or None)
    for line in plane.lines:
        if line.name not in (trace.OPS_LINE, trace.MODULES_LINE):
            continue
        t0 = line.timestamp_ns
        for e in line.events:
            name, op_name = meta.get(e.metadata_id, ("", None))
            start, dur = int(t0 + e.offset_ps / 1000), int(e.duration_ps
                                                           / 1000)
            if line.name == trace.OPS_LINE:
                out["ops"].append((name, start, dur, op_name))
            else:
                out["modules"].append((name, start, dur))
    out["modules"].sort(key=lambda e: e[1])
    return out


def save_mini(loaded: dict, threads: dict, path: str, t0_ns: int,
              t1_ns: int):
    """Cut ``load``'s result and ``spans.load``'s threads to [t0, t1) and
    keep them as small JSON: every device event with its times and its
    ``op_name`` (the instruction's name alone: its text is not needed
    here), every span with its stats.  ``load`` reads the ``parts`` of the
    file, ``spans.load`` its ``spans``."""
    def cut(events):
        return [list(e) for e in events if t0_ns <= e[1] < t1_ns]
    raw = {"parts": {"ops": [["%" + trace.short(e[0]) + " = ", *e[1:]]
                             for e in cut(loaded["ops"])],
                     "modules": cut(loaded["modules"])},
           "spans": {k: c for k, v in threads.items() if (c := cut(v))}}
    with gzip.open(path, "wt") as f:
        json.dump(raw, f, separators=(",", ":"))


def of_run(ctx):
    """The joined trace of the run whose readers share ``ctx``; ``None``
    without a device plane or where no program of the trace has a scope."""
    if ctx.get("trace") is None:
        return None
    if "parts" not in ctx:
        path, t = spans.newest_xplane(), time.time()
        loaded = load(path) if path else None
        ctx["parts"] = loaded if loaded and any(
            scopes_of(e[3])[0] for e in loaded["ops"]) else None
        # what the readers cost, after the run and outside every window
        print(f"cells: parts: {len(loaded['ops']) if loaded else 0} device "
              f"events joined to their op_name in {time.time() - t:.1f} s"
              + ("" if ctx["parts"] else "; no program scope among them"),
              flush=True)
    return ctx["parts"]


# ------------------------------------------------------------ attribution

def attributed(loaded: dict):
    """(rows, executions): ``rows`` = [(program, part, replay, self_ns,
    name, op_name)], one an ``XLA Ops`` event, program ``None`` outside
    every scoped execution; ``executions`` = [(program | None, start,
    dur)], one an ``XLA Modules`` event, by start.  An event without a
    scope of its own takes the program of the execution that encloses it,
    which is the program scope of most of that execution's scoped self
    time."""
    modules = loaded["modules"]
    op_names = {(e[0], e[1]): e[3] for e in loaded["ops"]}
    own = sorted(trace.self_times([e[:3] for e in loaded["ops"]]),
                 key=lambda e: e[1])
    per_module = [{} for _ in modules]  # program -> scoped self ns
    where, j = [], 0
    for name, start, dur, self_ns in own:
        while j < len(modules) and modules[j][1] + modules[j][2] <= start:
            j += 1
        inside = j if j < len(modules) and modules[j][1] <= start else None
        op_name = op_names[(name, start)]
        program, part = scopes_of(op_name)
        if inside is not None and program:
            acc = per_module[inside]
            acc[program] = acc.get(program, 0) + self_ns
        where.append((inside, program, part or UNSCOPED,
                      is_replay(name, op_name), self_ns, name, op_name))
    kinds = [max(acc, key=acc.get) if acc else None for acc in per_module]
    rows = [(program or (kinds[inside] if inside is not None else None),
             *rest) for inside, program, *rest in where]
    return rows, [(k, m[1], m[2]) for k, m in zip(kinds, modules)]


def _attributed(ctx):
    loaded = of_run(ctx)
    if loaded is None:
        return None
    if "parts_attributed" not in ctx:
        ctx["parts_attributed"] = attributed(loaded)
    return ctx["parts_attributed"]


def by_program_and_part(ctx):
    """{(program, part): seconds of chip 0's self time}; program ``None``:
    the programs without a scope (the engine's small ones)."""
    got = _attributed(ctx)
    if got is None:
        return None
    out = {}
    for program, part, _, self_ns, *_ in got[0]:
        out[(program, part)] = out.get((program, part), 0.0) + self_ns / 1e9
    return out


def executions(ctx, program):
    """[(start_ns, dur_ns)] of the ``XLA Modules`` executions of that
    program kind, by start."""
    got = _attributed(ctx)
    return None if got is None else [
        (s, d) for k, s, d in got[1] if k == program]


def program_pct(ctx, program):
    """Self time under ``program`` (its ``(unscoped)`` instructions
    included; ``None``: the programs without a scope) in percent of the
    traced window.  No such program in the trace is no reading, not 0."""
    table = by_program_and_part(ctx)
    if table is None or not ctx.get("trace_window_s") \
            or (program and not executions(ctx, program)):
        return None
    secs = sum(v for (p, _), v in table.items() if p == program)
    return 100.0 * secs / ctx["trace_window_s"]


def checked(ctx, program, tolerance=0.02):
    """{part: seconds} of ``program``, or ``None`` where the reader's check
    on itself fails: the parts (with ``(unscoped)``) must sum to the device
    time of the program's executions to within ``tolerance``."""
    table, runs = by_program_and_part(ctx), executions(ctx, program)
    if table is None or not runs:
        return None
    parts = {part: v for (p, part), v in table.items() if p == program}
    device = sum(d for _, d in runs) / 1e9
    if abs(sum(parts.values()) - device) > tolerance * device:
        return None
    return parts


def part_ms(ctx, program, match):
    """ms an execution of ``program`` spends in the parts ``match(part)``
    admits."""
    parts = checked(ctx, program)
    mine = [v for part, v in (parts or {}).items() if match(part)]
    if not mine:  # the check failed, or the program has no such part
        return None
    return 1e3 * sum(mine) / len(executions(ctx, program))


def replay_ms(ctx, program):
    """ms an execution of ``program`` spends in rematerialised
    instructions."""
    got, runs = _attributed(ctx), executions(ctx, program)
    if got is None or not runs or checked(ctx, program) is None:
        return None
    return sum(row[3] for row in got[0]
               if row[0] == program and row[2]) / 1e6 / len(runs)


# ------------------------------------------- a prefill and its admission

def matched_prefills(ctx):
    """[(device ns, prefilled_tokens, bucket)]: each traced prefill
    execution with the admission that launched it.

    A step's prefill programs start after the step's first ``engine.admit``
    span starts and end before its ``engine.first_tokens`` span ends (that
    span fetches their first tokens), in the order of the step's
    ``engine.admit`` spans with ``bucket`` > 0.  A step the traced window
    cuts (an admission before the first device operation, a fetch after
    the last, no ``engine.first_tokens`` at all) is dropped; a step whose
    counts disagree fails the reader (``None``), as does a trace without
    the ``prefilled_tokens`` stat."""
    threads, runs = spans.of_run(ctx), executions(ctx, "engine.prefill")
    if not threads or runs is None:
        return None
    events = spans.engine_thread(threads)
    if events is None:
        return None
    first, last = trace.span(ctx["trace"])
    out = []
    for _, s0, d0, _ in (e for e in events if e[0] == spans.STEP):
        inside = [e for e in events if s0 <= e[1] < s0 + d0]
        admits = sorted((e for e in inside if e[0] == "engine.admit"
                         and e[3].get("bucket", 0) > 0), key=lambda e: e[1])
        fetch = next((e for e in inside
                      if e[0] == "engine.first_tokens"), None)
        if not admits:
            continue
        if any("prefilled_tokens" not in e[3] for e in admits):
            return None
        if fetch is None or admits[0][1] < first \
                or fetch[1] + fetch[2] > last:
            continue
        t0, t1 = admits[0][1], fetch[1] + fetch[2]
        mine = [(s, d) for s, d in runs if s >= t0 and s + d <= t1]
        if len(mine) != len(admits):
            return None
        out += [(d, a[3]["prefilled_tokens"], a[3]["bucket"])
                for (_, d), a in zip(mine, admits)]
    return out


def prefill_us_per_token(ctx):
    rows = matched_prefills(ctx)
    tokens = sum(r[1] for r in rows) if rows else 0
    return sum(r[0] for r in rows) / 1e3 / tokens if tokens else None


def prefill_padding_pct(ctx):
    rows = matched_prefills(ctx)
    padded = sum(r[2] for r in rows) if rows else 0
    return 100.0 * (1 - sum(r[1] for r in rows) / padded) if padded \
        else None
