"""From a profiler trace (``.xplane.pb``) to numbers.

``load`` reads the file with ``jax.profiler.ProfileData`` (importing
``jax`` initialises no backend) into plain tuples; everything after that
is arithmetic on intervals, shared by the readers in ``layer_metrics/``
and checked by ``tests/`` on a recorded miniature trace.

What a v5e trace holds (looked at by hand, PR 23): one plane per chip,
``/device:TPU:<n>``.  Its line ``XLA Ops`` carries one event per executed
HLO instruction, named by the instruction's whole text (``%fusion.227 =
(f32[4096]...) fusion(...)``); a ``while`` is an event that encloses the
events of its body, so durations on the line nest and only a union or a
self time may be added up.  A Pallas (Mosaic) kernel is an instruction
whose text holds ``custom_call_target="tpu_custom_call"``, under whatever
name XLA gave it (``%closed_call.17``, ``%checkpoint.25``).  The line
``XLA Modules`` carries one event per executed program
(``jit__train_step(...)``, ``jit_paged_decode_sample(...)``).  Host threads
are lines of the plane ``/host:CPU`` and hold the Python tracer's frames
(``$api.py:3097 block_until_ready``).  Times are nanoseconds on one clock.
"""

import glob
import gzip
import json
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)")
MOSAIC = r'custom_call_target="tpu_custom_call"'
_NAME = re.compile(r"^%([\w.\-]+) = ")


def short(name: str) -> str:
    """``%fusion.227 = (f32[...`` -> ``fusion.227``."""
    m = _NAME.match(name)
    return m.group(1) if m else name[:60]


def find_xplane(trace_dir: str):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def load(path: str) -> dict:
    """{"device": {chip: {line: [(name, start_ns, dur_ns), ...]}},
    "host": {thread: [...]}} from an ``.xplane.pb`` or from the
    miniature ``.json.gz`` that ``save_mini`` wrote."""
    if path.endswith(".json.gz"):
        with gzip.open(path, "rt") as f:
            raw = json.load(f)
        return {"device": {int(k): {ln: [tuple(e) for e in ev]
                                    for ln, ev in v.items()}
                           for k, v in raw["device"].items()},
                "host": {k: [tuple(e) for e in v]
                         for k, v in raw["host"].items()}}
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"device": {}, "host": {}}
    for plane in data.planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if m:
            out["device"][int(m.group(1))] = {
                line.name: [(e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events]
                for line in plane.lines
                if line.name in (OPS_LINE, MODULES_LINE)}
        elif plane.name.startswith("/host:CPU"):
            for i, line in enumerate(plane.lines):  # names repeat: number
                out["host"][f"{line.name}#{i}"] = [
                    (e.name, int(e.start_ns), int(e.duration_ns))
                    for e in line.events]
    return out


def save_mini(trace: dict, path: str, t0_ns: int, t1_ns: int,
              host_min_ns: int = 200_000):
    """Cut a loaded trace to [t0, t1) and keep it as small JSON: the
    recorded miniature the tests reduce."""
    def cut(events, floor=0):
        return [e for e in events
                if t0_ns <= e[1] < t1_ns and e[2] >= floor]
    raw = {"device": {str(k): {ln: cut(ev) for ln, ev in v.items()}
                      for k, v in trace["device"].items()},
           "host": {k: c for k, v in trace["host"].items()
                    if (c := cut(v, host_min_ns))}}
    with gzip.open(path, "wt") as f:
        json.dump(raw, f, separators=(",", ":"))


# ------------------------------------------------------------ intervals

def merge(intervals):
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(merged) -> int:
    return sum(e - s for s, e in merged)


def subtract(a, b):
    """Parts of merged ``a`` that no interval of merged ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def ops(trace, chip):
    return trace["device"][chip].get(OPS_LINE, [])


def modules(trace, chip):
    return trace["device"][chip].get(MODULES_LINE, [])


def span(trace):
    """(first start, last end) over every device operation, ns."""
    starts = [e[1] for c in trace["device"] for e in ops(trace, c)]
    ends = [e[1] + e[2] for c in trace["device"] for e in ops(trace, c)]
    return (min(starts), max(ends)) if starts else (0, 0)


def busy_ns(trace, chip) -> int:
    """Length of the union of the intervals in which an operation ran."""
    return length(merge((s, s + d) for _, s, d in ops(trace, chip)))


def busy_s_mean(trace) -> float:
    chips = list(trace["device"])
    if not chips:
        return 0.0
    return sum(busy_ns(trace, c) for c in chips) / len(chips) / 1e9


def idle_pct(trace, window_s):
    """1 - busy / window, in percent; None without a trace or a window."""
    if trace is None or not window_s:
        return None
    return 100.0 * (1.0 - busy_s_mean(trace) / window_s)


def exposed_collective_ns(trace, chip) -> int:
    """Time a collective runs on the chip while no other instruction does.
    Leaves only: a ``while`` that encloses a collective is not compute."""
    coll, comp = [], []
    for name, start, dur, own in self_times(ops(trace, chip)):
        if own == dur:  # a leaf: nothing nested inside it
            (coll if COLLECTIVE.match(short(name)) else comp).append(
                (start, start + dur))
    return length(subtract(merge(coll), merge(comp)))


def self_times(events):
    """[(name, start, dur, self)]: each event's duration less that of the
    events nested directly inside it (a ``while`` holds its body's)."""
    out, stack = [], []
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] + stack[-1][2] < start + dur:
            stack.pop()  # ended before this one ends: not its parent
        row = [name, start, dur, dur]
        if stack:
            stack[-1][3] -= dur
        stack.append(row)
        out.append(row)
    return [tuple(r) for r in out]


def op_time_s(trace, pattern: str, line=OPS_LINE):
    """(summed seconds, count) of the events whose text matches, averaged
    over chips.  For leaf instructions (a kernel, a fusion): durations of
    nested events would count twice."""
    rx = re.compile(pattern)
    chips = list(trace["device"])
    total = count = 0
    for c in chips:
        for n, _, d in trace["device"][c].get(line, []):
            if rx.search(n):
                total += d
                count += 1
    k = max(1, len(chips))
    return total / k / 1e9, count / k


def module_durations_s(trace, pattern: str):
    """Device seconds of each execution of the programs whose name
    matches, on the first chip."""
    rx = re.compile(pattern)
    chips = sorted(trace["device"])
    if not chips:
        return []
    return [d / 1e9 for n, _, d in modules(trace, chips[0]) if rx.search(n)]


def decode_program_s(trace):
    """Device seconds of each execution of the engine's decode program.
    ``LLMEngine`` jits ``functools.partial`` objects, which have no name,
    so the decode step and every prefill bucket are all ``jit__unknown``
    with a fingerprint; the decode step is the one executed most often
    (16 times a window, against one prefill a request)."""
    chips = sorted(trace["device"])
    if not chips:
        return []
    by_name = {}
    for name, _, dur in modules(trace, chips[0]):
        if name.startswith("jit__unknown"):
            by_name.setdefault(name, []).append(dur / 1e9)
    return max(by_name.values(), key=len, default=[])


# ------------------------------------------------------------ breakdown

def _family(name: str) -> str:
    """``%fusion.123 = ...`` -> ``fusion``: instances of one kind add up.
    A Mosaic kernel keeps its own name."""
    if re.search(MOSAIC, name):
        return "tpu_custom_call:" + short(name)
    return re.sub(r"[.\d]+$", "", short(name)) or short(name)


def top_device_ops(trace, n=10):
    """[[name, seconds], ...]: the instruction families with the most
    self time on the device, averaged over chips."""
    chips = list(trace["device"])
    acc = {}
    for c in chips:
        for name, _, _, own in self_times(ops(trace, c)):
            key = _family(name)
            acc[key] = acc.get(key, 0) + own
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / max(1, len(chips)) / 1e9] for k, v in rows]


def dispatch_thread(trace):
    """The host thread that hands programs to the device: the line with
    the most time in JAX's own dispatch events."""
    def weight(events):
        return sum(d for name, _, d in events
                   if name.startswith(("PjitFunction", "np.asarray"))
                   or "block_until_ready" in name)
    best = max(trace["host"], key=lambda k: weight(trace["host"][k]),
               default=None)
    return best if best is not None and weight(trace["host"][best]) else None


def idle_gaps(trace, n=10, longest=400):
    """[[what the host was doing, seconds], ...]: idle time of the first
    chip (its ``longest`` gaps over 20 us), each gap attributed to the
    innermost event of the dispatching host thread that covers most of
    it, summed per label; ``(no host event)`` where none does."""
    import numpy as np

    chips = sorted(trace["device"])
    if not chips:
        return []
    busy = merge((s, s + d) for _, s, d in ops(trace, chips[0]))
    gaps = sorted(((busy[i + 1][0] - busy[i][1], busy[i][1], busy[i + 1][0])
                   for i in range(len(busy) - 1)
                   if busy[i + 1][0] - busy[i][1] > 20_000),
                  reverse=True)[:longest]
    thread = dispatch_thread(trace)
    host = [(s, s + d, name) for name, s, d in trace["host"].get(thread, [])
            if d > 10_000]
    starts = np.array([h[0] for h in host], np.int64)
    ends = np.array([h[1] for h in host], np.int64)
    acc = {}
    for size, g0, g1 in gaps:
        label = "(no host event)"
        if len(host):
            overlap = np.minimum(ends, g1) - np.maximum(starts, g0)
            covering = np.nonzero(overlap > 0.5 * size)[0]
            if len(covering):
                inner = covering[np.argmin((ends - starts)[covering])]
                label = host[inner][2]
        acc[label] = acc.get(label, 0) + size
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k[:80], v / 1e9] for k, v in rows]
