"""raylint tier: fixture self-tests per checker, the live-tree gate, and
the CLI exit-code contract.

Three layers:

1. **Fixture self-tests** — for every checker a known-bad snippet it
   must flag (true positive) and the corrected snippet it must pass
   (true negative), so a checker regression is caught like any other
   code.  The fixtures double as the migration proof for the guards
   that moved here from test_tooling.py (fault-site-coverage,
   proxy-request-context, collective-supervision, serial-blocking-get).
2. **Live-tree gate** — one parametrized test per rule over the real
   repo: zero unsuppressed findings, every suppression carries a
   reason.  This is the tier-1 enforcement the checkers exist for.
3. **CLI contract** — ``raytpu lint --format=json`` exits 0 clean /
   1 findings / 2 internal error.
"""

import json
import os
import subprocess
import sys

import pytest

from ray_tpu._private.analysis import all_rules, run_lint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint_tree(tmp_path, files, rules=None):
    """Write ``files`` (relpath -> source) under a tmp root and lint it."""
    for rel, src in files.items():
        path = tmp_path / rel
        if src is None:  # marker for "this file is absent from the tree"
            if path.exists():
                path.unlink()  # earlier calls share the tmp root
            continue
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(src)
    return run_lint(str(tmp_path), rules=rules)


def rules_of(result):
    return [f.rule for f in result.findings]


# ---------------------------------------------------------------------------
# fixture self-tests: one bad + one good per checker
# ---------------------------------------------------------------------------

def test_thread_lifecycle_fixtures(tmp_path):
    bad = """import threading

class Pump:
    def start(self):
        self._t = threading.Thread(target=self._run)
        self._t.start()

    def _run(self):
        pass
"""
    r = lint_tree(tmp_path, {"ray_tpu/bad.py": bad},
                  rules=["thread-lifecycle"])
    assert rules_of(r) == ["thread-lifecycle"], r.findings

    good = """import threading

class Pump:
    def start(self):
        self._t = threading.Thread(target=self._run, daemon=True)
        self._t.start()
        t2 = threading.Thread(target=self._run)
        t2.start()
        t2.join()

    def _run(self):
        pass

class Joined:
    def start(self):
        self._t = threading.Thread(target=self._run)
        self._t.start()

    def stop(self):
        self._t.join()

    def _run(self):
        pass
"""
    r = lint_tree(tmp_path, {"ray_tpu/bad.py": good},
                  rules=["thread-lifecycle"])
    assert not r.findings, r.findings


def test_span_hygiene_fixtures(tmp_path):
    bad = """from ray_tpu._private import tracing

class Loop:
    def begin(self):
        self._span = tracing.span("loop")  # stashed, never entered

def leak_cm():
    s = tracing.span("work")  # CM stashed instead of with-entered
    return s

def leak_trace():
    t = tracing.trace("request")
    return t
"""
    r = lint_tree(tmp_path, {"ray_tpu/bad.py": bad},
                  rules=["span-hygiene"])
    assert rules_of(r) == ["span-hygiene"] * 3, r.findings

    good = """from ray_tpu._private import tracing

def lexical():
    with tracing.span("work"):
        pass
    with tracing.trace("request") as ctx:
        return ctx

def in_a_program(x):
    with tracing.scope("attn.proj"):  # a name scope, not a span
        return x

def span(name):  # a user's own function of that name
    s = span(name)
    return s
"""
    r = lint_tree(tmp_path, {"ray_tpu/bad.py": good},
                  rules=["span-hygiene"])
    assert not r.findings, r.findings


def test_bounded_blocking_fixtures(tmp_path):
    bad = """import queue

class Box:
    def __init__(self):
        self._q = queue.Queue(maxsize=2)

    def send(self, x):
        self._q.put(x)

    def recv(self):
        return self._q.get()
"""
    r = lint_tree(tmp_path, {"ray_tpu/bad.py": bad},
                  rules=["bounded-blocking"])
    assert rules_of(r) == ["bounded-blocking"] * 2, r.findings

    good = """import queue

class Box:
    def __init__(self):
        self._q = queue.Queue(maxsize=2)
        self._logq = queue.Queue()  # unbounded: put can never block

    def send(self, x):
        self._q.put(x, timeout=1.0)
        self._q.put_nowait(x)
        self._logq.put(x)

    def recv(self):
        return self._q.get(timeout=1.0)
"""
    r = lint_tree(tmp_path, {"ray_tpu/bad.py": good},
                  rules=["bounded-blocking"])
    assert not r.findings, r.findings


def test_bounded_blocking_serve_get_fixtures(tmp_path):
    bad = "import ray_tpu\n\ndef f(ref):\n    return ray_tpu.get(ref)\n"
    # the deadline-required set: serve/ (the latency-critical control
    # plane), rl/ (long-lived loops over killable rollout/learner
    # actors — the RLHF-crucible rule), and llm/ (KV-handoff plane
    # between killable prefill/decode replicas)
    r = lint_tree(tmp_path, {"ray_tpu/serve/mod.py": bad,
                             "ray_tpu/rl/mod.py": bad,
                             "ray_tpu/llm/mod.py": bad},
                  rules=["bounded-blocking"])
    assert rules_of(r) == ["bounded-blocking"] * 3, r.findings
    assert {f.path for f in r.findings} == \
        {"ray_tpu/serve/mod.py", "ray_tpu/rl/mod.py",
         "ray_tpu/llm/mod.py"}
    # same code outside the deadline set is NOT the control plane
    r = lint_tree(tmp_path, {"ray_tpu/serve/mod.py": "",
                             "ray_tpu/rl/mod.py": "",
                             "ray_tpu/llm/mod.py": "",
                             "ray_tpu/other.py": bad},
                  rules=["bounded-blocking"])
    assert not r.findings, r.findings
    good = ("import ray_tpu\n\ndef f(ref):\n"
            "    return ray_tpu.get(ref, timeout=5)\n")
    r = lint_tree(tmp_path, {"ray_tpu/serve/mod.py": good,
                             "ray_tpu/rl/mod.py": good,
                             "ray_tpu/llm/mod.py": good,
                             "ray_tpu/other.py": ""},
                  rules=["bounded-blocking"])
    assert not r.findings, r.findings


def test_bounded_blocking_checkpoint_replica_fixtures(tmp_path):
    """util/checkpoint_replica.py is deadline-required as a single
    file (not a directory): every push/fetch targets a peer-RAM
    replica server on another host that may be SIGKILLed mid-RPC —
    the exact death the tier exists to survive — so a bare
    ``ray_tpu.get`` there would wedge the persist thread forever."""
    bad = "import ray_tpu\n\ndef push(ref):\n    return ray_tpu.get(ref)\n"
    r = lint_tree(tmp_path, {"ray_tpu/util/checkpoint_replica.py": bad},
                  rules=["bounded-blocking"])
    assert rules_of(r) == ["bounded-blocking"], r.findings
    assert r.findings[0].path == "ray_tpu/util/checkpoint_replica.py"
    # the rest of util/ stays out of the deadline set — only the
    # replica plane file is control-plane
    r = lint_tree(tmp_path, {"ray_tpu/util/checkpoint_replica.py": "",
                             "ray_tpu/util/other.py": bad},
                  rules=["bounded-blocking"])
    assert not r.findings, r.findings
    good = ("import ray_tpu\n\ndef push(ref):\n"
            "    return ray_tpu.get(ref, timeout=30.0)\n")
    r = lint_tree(tmp_path, {"ray_tpu/util/checkpoint_replica.py": good,
                             "ray_tpu/util/other.py": ""},
                  rules=["bounded-blocking"])
    assert not r.findings, r.findings


def test_bounded_blocking_llm_channel_read_fixtures(tmp_path):
    """llm/ is a deadline-required dir for channel reads too: a KV
    landing loop whose prefill peer died must poll with a bound, never
    park forever on a channel nobody will write."""
    bad = """from ray_tpu.experimental.channel.transport import (
    attach_edge_transport, make_edge_transport)

def land(info):
    tr = attach_edge_transport(info, 0)
    return tr.read()          # TP: no deadline
"""
    r = lint_tree(tmp_path, {"ray_tpu/llm/mod.py": bad},
                  rules=["bounded-blocking"])
    assert rules_of(r) == ["bounded-blocking"], r.findings
    good = """from ray_tpu.experimental.channel.transport import (
    attach_edge_transport, make_edge_transport)

def land(info):
    tr = attach_edge_transport(info, 0)
    return tr.read(timeout=0.25)   # TN: bounded poll
"""
    r = lint_tree(tmp_path, {"ray_tpu/llm/mod.py": good},
                  rules=["bounded-blocking"])
    assert not r.findings, r.findings


def test_bounded_blocking_channel_read_fixtures(tmp_path):
    """Deadline-required dirs (now incl. experimental/channel/ and dag/):
    every channel read needs a bound — a dead peer never writes, so a
    bare read wedges the exec loop / pipeline stage forever."""
    bad = """from ray_tpu.experimental.channel import Channel, EdgeTransport

def f():
    ch = Channel(buffer_size=1 << 12, num_readers=1)
    rc = Channel(ch.name, num_readers=1, _create=False).set_reader_slot(0)
    tr = EdgeTransport(ch)
    a = rc.read()            # TP: no deadline
    b = tr.read_bytes()      # TP: no deadline
    c = tr.read_borrowed(float)  # TP: fn only, no deadline
    return a, b, c
"""
    # the rule binds in every deadline dir, incl. the two new ones
    r = lint_tree(tmp_path, {"ray_tpu/experimental/channel/mod.py": bad,
                             "ray_tpu/dag/mod.py": bad},
                  rules=["bounded-blocking"])
    assert rules_of(r) == ["bounded-blocking"] * 6, r.findings
    assert {f.path for f in r.findings} == \
        {"ray_tpu/experimental/channel/mod.py", "ray_tpu/dag/mod.py"}
    # same code outside the deadline set is not flagged (TN), and
    # bounded reads inside it are clean (TN)
    good = """from ray_tpu.experimental.channel import Channel, EdgeTransport

def f():
    ch = Channel(buffer_size=1 << 12, num_readers=1)
    tr = EdgeTransport(ch)
    a = ch.read(0.5)                      # positional timeout
    b = tr.read(timeout=None)             # explicit deadline decision
    c = tr.read_borrowed(float, timeout=2)
    d = open("/dev/null").read()          # not a channel receiver
    return a, b, c, d
"""
    r = lint_tree(tmp_path, {"ray_tpu/experimental/channel/mod.py": "",
                             "ray_tpu/dag/mod.py": good,
                             "ray_tpu/other.py": bad},
                  rules=["bounded-blocking"])
    assert not r.findings, r.findings


def test_async_purity_fixtures(tmp_path):
    bad = """import time
import ray_tpu

async def handler(ref, sock):
    time.sleep(0.1)
    x = ray_tpu.get(ref)
    ray_tpu.wait([ref], fetch_local=True)
    return x + sock.recv(1)
"""
    r = lint_tree(tmp_path, {"ray_tpu/serve/mod.py": bad},
                  rules=["async-purity"])
    assert rules_of(r) == ["async-purity"] * 4, r.findings

    good = """import asyncio
import time
import ray_tpu

async def handler(ref, loop):
    await asyncio.sleep(0.1)
    x = await loop.run_in_executor(None, ray_tpu.get, ref)
    ray_tpu.wait([ref], fetch_local=False)

    def blocking_helper():  # runs in an executor, not on the loop
        time.sleep(0.1)
        return ray_tpu.get(ref)

    y = await loop.run_in_executor(None, lambda: ray_tpu.get(ref))
    return x, y, blocking_helper
"""
    r = lint_tree(tmp_path, {"ray_tpu/serve/mod.py": good},
                  rules=["async-purity"])
    assert not r.findings, r.findings
    # the rule is scoped to event-loop-hosted packages
    r = lint_tree(tmp_path, {"ray_tpu/serve/mod.py": "",
                             "ray_tpu/data/mod.py": bad},
                  rules=["async-purity"])
    assert not r.findings, r.findings


def test_lock_discipline_fixtures(tmp_path):
    bad = """import threading

class Watcher:
    def __init__(self):
        self._lock = threading.Lock()
        self._t = threading.Thread(target=self._loop, daemon=True)
        self.state = {}

    def _loop(self):
        self.state["tick"] = 1

    def poke(self):
        self.state = {}
"""
    r = lint_tree(tmp_path, {"ray_tpu/bad.py": bad},
                  rules=["lock-discipline"])
    assert rules_of(r) == ["lock-discipline"] * 2, r.findings

    good = """import threading

class Watcher:
    def __init__(self):
        self._lock = threading.Lock()
        self._t = threading.Thread(target=self._loop, daemon=True)
        self.state = {}

    def _loop(self):
        with self._lock:
            self.state["tick"] = 1

    def poke(self):
        with self._lock:
            self.state = {}

class NoThreads:  # classes that never start a thread are exempt
    def __init__(self):
        self.state = {}

    def _loop(self):
        self.state["tick"] = 1

    def poke(self):
        self.state = {}
"""
    r = lint_tree(tmp_path, {"ray_tpu/bad.py": good},
                  rules=["lock-discipline"])
    assert not r.findings, r.findings


def test_context_capture_fixtures(tmp_path):
    bad = """from ray_tpu.data.context import DataContext

class It:
    def iter_batches(self):
        return DataContext.get_current().prefetch_batches
"""
    r = lint_tree(tmp_path, {"ray_tpu/data/mod.py": bad},
                  rules=["context-capture"])
    assert rules_of(r) == ["context-capture"], r.findings

    good = """from ray_tpu.data.context import DataContext

def plan():  # module-level functions are driver-side planning code
    return DataContext.get_current().prefetch_batches

class It:
    def __init__(self):  # capture at construction: travels with self
        self._prefetch = DataContext.get_current().prefetch_batches

    def iter_batches(self):
        return self._prefetch
"""
    r = lint_tree(tmp_path, {"ray_tpu/data/mod.py": good},
                  rules=["context-capture"])
    assert not r.findings, r.findings


def test_serial_blocking_get_fixtures(tmp_path):
    bad = """import ray_tpu

def gen(refs):
    for r in refs:
        yield ray_tpu.get(r)
"""
    r = lint_tree(tmp_path, {"ray_tpu/data/iterator.py": bad},
                  rules=["serial-blocking-get"])
    assert rules_of(r) == ["serial-blocking-get"], r.findings

    good = """import ray_tpu

def gen(refs):
    blocks = ray_tpu.get([r for r in refs])  # batched: one round trip
    for b in blocks:
        yield b

def gen2(refs):
    for r in refs:
        yield ray_tpu.get(r)  # raylint: disable=serial-blocking-get -- fixture: pull provably started at admission
"""
    r = lint_tree(tmp_path, {"ray_tpu/data/iterator.py": good},
                  rules=["serial-blocking-get"])
    assert not r.findings, r.findings
    assert len(r.suppressed) == 1
    # the rule is scoped to the ingest hot files
    r = lint_tree(tmp_path, {"ray_tpu/data/iterator.py": "",
                             "ray_tpu/data/other.py": bad},
                  rules=["serial-blocking-get"])
    assert not r.findings, r.findings


def test_test_hygiene_fixtures(tmp_path):
    bad = """import subprocess

import ray_tpu


@ray_tpu.remote
def _helper():
    return 1


def _kill_workers():
    subprocess.run(["pkill", "-f", "worker_proc"])
"""
    r = lint_tree(tmp_path, {"tests/test_mod.py": bad},
                  rules=["test-hygiene"])
    assert rules_of(r) == ["test-hygiene"] * 2, r.findings

    good = """import os
import signal

import ray_tpu


def test_things():
    @ray_tpu.remote
    def _helper():
        return 1

    assert ray_tpu.get(_helper.remote()) == 1


def _kill_worker(pid):
    os.kill(pid, signal.SIGKILL)  # exact pid, never a name pattern
"""
    r = lint_tree(tmp_path, {"tests/test_mod.py": good},
                  rules=["test-hygiene"])
    assert not r.findings, r.findings
    # source files outside tests/ are not in scope
    r = lint_tree(tmp_path, {"tests/test_mod.py": "",
                             "ray_tpu/mod.py": bad},
                  rules=["test-hygiene"])
    assert not r.findings, r.findings


def test_sharding_discipline_fixtures(tmp_path):
    bad = """import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def decoder_layer(x, mesh):
    x = jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P("dp", None, "tp")))
    spec = P(("dp", "fsdp"))
    return x, spec
"""
    # two findings: the raw constraint call AND the device-axis literal
    # in the same expression, plus the second bare literal
    r = lint_tree(tmp_path, {"ray_tpu/models/bad.py": bad},
                  rules=["sharding-discipline"])
    assert rules_of(r) == ["sharding-discipline"] * 3, r.findings

    good = """from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.parallel.sharding import (
    logical_to_pspec,
    spec_tree_to_shardings,
    with_logical_constraint,
)


def decoder_layer(x, mesh, rules):
    x = with_logical_constraint(x, mesh, "batch", "seq", None, rules=rules)
    batch_spec = logical_to_pspec(("batch",), rules, mesh=mesh)
    replicated = NamedSharding(mesh, P())  # no device axis named: legal
    empty = P(None)
    return x, batch_spec, replicated, empty
"""
    r = lint_tree(tmp_path, {"ray_tpu/models/bad.py": good},
                  rules=["sharding-discipline"])
    assert not r.findings, r.findings

    # scope: the rule owns models/ only — the parallel substrate and
    # trainers elsewhere legitimately build NamedShardings
    r = lint_tree(tmp_path, {"ray_tpu/models/bad.py": "",
                             "ray_tpu/parallel/impl.py": bad,
                             "benchmarks/crucible.py": bad},
                  rules=["sharding-discipline"])
    assert not r.findings, r.findings


def test_bench_emission_fixtures(tmp_path):
    bad = """import json


def main():
    print(json.dumps({"metric": "m", "value": 1}))


if __name__ == "__main__":
    main()
"""
    # two findings: the hand-printed bare-JSON record AND the missing
    # final-record emission
    r = lint_tree(tmp_path, {"benchmarks/bad_bench.py": bad},
                  rules=["bench-emission"])
    assert rules_of(r) == ["bench-emission"] * 2, r.findings

    good = """import json

from ray_tpu._private.bench_emit import emit_final_record, emit_record_line


def main():
    emit_record_line({"config": "intermediate"})
    print("TIMINGS " + json.dumps({"x": 1}))  # prefixed: legal
    emit_final_record({"metric": "m", "value": 1})


if __name__ == "__main__":
    main()
"""
    r = lint_tree(tmp_path, {"benchmarks/bad_bench.py": good},
                  rules=["bench-emission"])
    assert not r.findings, r.findings

    # running the body under final_record_guard satisfies the contract
    guarded = """from ray_tpu._private.bench_emit import final_record_guard


def main():
    with final_record_guard("m") as out:
        out["record"] = {"metric": "m", "value": 1}


if __name__ == "__main__":
    main()
"""
    r = lint_tree(tmp_path, {"benchmarks/bad_bench.py": guarded},
                  rules=["bench-emission"])
    assert not r.findings, r.findings

    # importable helper modules (no __main__ guard) are exempt, and so
    # are bare-JSON prints outside the benchmark file set
    helper = """import json


def report(rec):
    print(json.dumps(rec))
"""
    r = lint_tree(tmp_path, {"benchmarks/bad_bench.py": helper,
                             "ray_tpu/mod.py": bad},
                  rules=["bench-emission"])
    assert not r.findings, r.findings


# -- migrated project-checker fixtures --------------------------------------

_FI_DOC = '''"""Fault injection registry.

Sites currently wired:

``ingest.pull``      the block pull edge
"""

def fault_point(site):
    pass
'''


def test_fault_site_coverage_fixtures(tmp_path):
    caller = ("from ray_tpu.util.fault_injection import fault_point\n\n"
              "def pull():\n    fault_point(\"ingest.pull\")\n")
    undocumented = ("from ray_tpu.util.fault_injection import fault_point"
                    "\n\ndef push():\n    fault_point(\"ingest.push\")\n")
    tree = {
        "ray_tpu/util/fault_injection.py": _FI_DOC,
        "ray_tpu/mod.py": caller,
        "docs/fault_tolerance.md": "## Sites\n\n`ingest.pull` guards x\n",
    }
    r = lint_tree(tmp_path, dict(tree), rules=["fault-site-coverage"])
    assert not r.findings, r.findings

    # an undocumented site is flagged twice: docs + module docstring
    tree["ray_tpu/mod2.py"] = undocumented
    r = lint_tree(tmp_path, tree, rules=["fault-site-coverage"])
    assert rules_of(r) == ["fault-site-coverage"] * 2, r.findings
    assert all("ingest.push" in f.message for f in r.findings)

    # sites without the registry module: the rule does not silently
    # vanish — the missing registry is itself the finding (the docs
    # half still runs)
    del tree["ray_tpu/mod2.py"]
    tree["ray_tpu/util/fault_injection.py"] = None
    r = lint_tree(tmp_path, tree, rules=["fault-site-coverage"])
    assert any("registry module is missing" in f.message
               for f in r.findings), r.findings


_PROXY_GOOD = """def new_request_context(route, timeout_s=None):
    return object()

def scope(ctx):
    return ctx

async def handler(request, handle):
    ctx = new_request_context(request, timeout_s=1.0)
    with scope(ctx):
        resp = handle.remote(request)
    return resp
"""

_PROXY_BAD = """async def handler(request, handle):
    return handle.remote(request)
"""


def _proxy_tree(proxy=None, grpc=None):
    return {"ray_tpu/serve/proxy.py": _PROXY_GOOD if proxy is None
            else proxy,
            "ray_tpu/serve/grpc_proxy.py": _PROXY_GOOD if grpc is None
            else grpc}


def test_proxy_request_context_fixtures(tmp_path):
    r = lint_tree(tmp_path, _proxy_tree(),
                  rules=["proxy-request-context"])
    assert not r.findings, r.findings

    r = lint_tree(tmp_path, _proxy_tree(proxy=_PROXY_BAD),
                  rules=["proxy-request-context"])
    got = rules_of(r)
    # unscoped dispatch + no mint in module + handler never mints
    assert got == ["proxy-request-context"] * 3, r.findings

    # a mint without timeout_s is its own finding
    lazy = _PROXY_GOOD.replace(
        "new_request_context(request, timeout_s=1.0)",
        "new_request_context(request)")
    r = lint_tree(tmp_path, _proxy_tree(proxy=lazy),
                  rules=["proxy-request-context"])
    assert any("timeout_s" in f.message for f in r.findings), r.findings

    # a renamed/deleted sibling proxy module is flagged, not skipped
    r = lint_tree(tmp_path, {"ray_tpu/serve/proxy.py": _PROXY_GOOD,
                             "ray_tpu/serve/grpc_proxy.py": None},
                  rules=["proxy-request-context"])
    assert [f.path for f in r.findings] == ["ray_tpu/serve/grpc_proxy.py"]


_OPS = ("allreduce", "reduce", "broadcast", "allgather",
        "reducescatter", "barrier", "send", "recv")

_SUPERVISION_TMPL = """def _supervised(fn):
    fn.__supervised__ = True
    return fn

class SupervisedGroup:
{methods}
"""

_COLLECTIVE_GOOD = """class SupervisedGroup:
    pass

class GroupManager:
    def get(self, group_name):
        return self._groups[group_name]

    def create(self, backend):
        return SupervisedGroup(backend)

_group_mgr = GroupManager()

def allreduce(tensor, group_name="default"):
    return _group_mgr.get(group_name).allreduce(tensor)
"""

_BASE_GOOD = """import abc

class BaseGroup(abc.ABC):
    @abc.abstractmethod
    def allreduce(self, tensor): ...

    @abc.abstractmethod
    def destroy_group(self): ...
"""


def _supervision_src(skip_decorator_on=None):
    methods = []
    for op in _OPS:
        if op != skip_decorator_on:
            methods.append("    @_supervised")
        methods.append(f"    def {op}(self, *a, **k):\n"
                       f"        return self._inner.{op}(*a, **k)\n")
    return _SUPERVISION_TMPL.format(methods="\n".join(methods))


def _collective_tree(**overrides):
    base = "ray_tpu/util/collective/"
    tree = {
        base + "supervision.py": _supervision_src(),
        base + "collective.py": _COLLECTIVE_GOOD,
        base + "collective_group/base_collective_group.py": _BASE_GOOD,
    }
    tree.update({base + k: v for k, v in overrides.items()})
    return tree


def test_collective_supervision_fixtures(tmp_path):
    r = lint_tree(tmp_path, _collective_tree(),
                  rules=["collective-supervision"])
    assert not r.findings, r.findings

    # an op that loses @_supervised is flagged
    r = lint_tree(
        tmp_path,
        _collective_tree(**{
            "supervision.py": _supervision_src(skip_decorator_on="send")}),
        rules=["collective-supervision"])
    assert [f.rule for f in r.findings] == ["collective-supervision"]
    assert "send" in r.findings[0].message

    # a new abstract backend op outside the supervised surface is flagged
    grown = _BASE_GOOD + ("\n    @abc.abstractmethod\n"
                          "    def fused_allreduce(self, tensor): ...\n")
    r = lint_tree(
        tmp_path,
        _collective_tree(**{
            "collective_group/base_collective_group.py": grown}),
        rules=["collective-supervision"])
    assert any("fused_allreduce" in f.message for f in r.findings)

    # a public op dispatching around the registry is flagged
    rogue = _COLLECTIVE_GOOD + (
        "\ndef barrier(group_name=\"default\"):\n"
        "    return _backends[group_name].barrier()\n")
    r = lint_tree(tmp_path, _collective_tree(**{"collective.py": rogue}),
                  rules=["collective-supervision"])
    assert any("barrier" in f.message for f in r.findings)


_GCS_BAD = '''\
_READONLY_HANDLERS = frozenset({"get_all_nodes", "ghost_verb"})

GCS_VERB_IDEMPOTENCY = {
    "register_node": "deduped",
    "kv_put": "sideways",
    "gone_verb": "idempotent",
    "get_all_nodes": "idempotent",
}


class GcsServer:
    async def handle_register_node(self, node_id):
        return {}

    async def handle_kv_put(self, key, value):
        return True

    async def handle_get_all_nodes(self):
        return []

    async def handle_unannotated(self):
        return True
'''

_GCS_GOOD = '''\
_READONLY_HANDLERS = frozenset({"get_all_nodes"})

GCS_VERB_IDEMPOTENCY = {
    "register_node": "deduped",
    "kv_put": "idempotent",
}


class GcsServer:
    async def handle_register_node(self, node_id):
        return {}

    async def handle_kv_put(self, key, value):
        return True

    async def handle_get_all_nodes(self):
        return []
'''


def test_gcs_verb_idempotency_fixtures(tmp_path):
    # the checker only audits the real GCS module path
    r = lint_tree(tmp_path, {"ray_tpu/_private/gcs.py": _GCS_BAD},
                  rules=["gcs-verb-idempotency"])
    msgs = sorted(f.message for f in r.findings)
    assert [f.rule for f in r.findings] == ["gcs-verb-idempotency"] * 5, msgs
    joined = "\n".join(msgs)
    assert "'unannotated' is not annotated" in joined          # missing
    assert "'sideways'" in joined                              # bad kind
    assert "'gone_verb' names no handle_gone_verb" in joined   # stale table
    assert "'ghost_verb' names no handle_ghost_verb" in joined  # stale ro
    assert "both read-only and mutating" in joined             # overlap

    r = lint_tree(tmp_path, {"ray_tpu/_private/gcs.py": _GCS_GOOD},
                  rules=["gcs-verb-idempotency"])
    assert not r.findings, r.findings

    # a computed registry defeats the static audit: reported loudly
    computed = _GCS_GOOD.replace('frozenset({"get_all_nodes"})',
                                 "frozenset(_build_readonly())")
    r = lint_tree(tmp_path, {"ray_tpu/_private/gcs.py": computed},
                  rules=["gcs-verb-idempotency"])
    assert [f.rule for f in r.findings] == ["gcs-verb-idempotency"]
    assert "_READONLY_HANDLERS" in r.findings[0].message

    # no handle_register_node class at all: the audit is broken, say so
    headless = "GCS_VERB_IDEMPOTENCY = {}\n_READONLY_HANDLERS = frozenset()\n"
    r = lint_tree(tmp_path, {"ray_tpu/_private/gcs.py": headless},
                  rules=["gcs-verb-idempotency"])
    assert any("cannot find the GCS server class" in f.message
               for f in r.findings)

    # some OTHER file defining handle_* verbs is not this checker's business
    r = lint_tree(tmp_path, {"ray_tpu/_private/gcs.py": _GCS_GOOD,
                             "ray_tpu/other.py": _GCS_BAD},
                  rules=["gcs-verb-idempotency"])
    assert not r.findings, r.findings


# ---------------------------------------------------------------------------
# engine semantics: suppressions + syntax errors
# ---------------------------------------------------------------------------

def test_suppression_requires_reason(tmp_path):
    src = ("import ray_tpu\n\ndef f(ref):\n"
           "    return ray_tpu.get(ref)  # raylint: disable=bounded-blocking\n")
    r = lint_tree(tmp_path, {"ray_tpu/serve/mod.py": src},
                  rules=["bounded-blocking"])
    assert sorted(rules_of(r)) == ["bounded-blocking",
                                   "suppression-hygiene"], r.findings

    with_reason = src.replace(
        "disable=bounded-blocking",
        "disable=bounded-blocking -- fixture: peer provably alive")
    r = lint_tree(tmp_path, {"ray_tpu/serve/mod.py": with_reason},
                  rules=["bounded-blocking"])
    assert not r.findings and len(r.suppressed) == 1
    assert r.suppressed[0].suppress_reason == "fixture: peer provably alive"


def test_suppression_line_above_and_wrong_rule(tmp_path):
    above = ("import ray_tpu\n\ndef f(ref):\n"
             "    # raylint: disable=bounded-blocking -- fixture reason\n"
             "    return ray_tpu.get(ref)\n")
    r = lint_tree(tmp_path, {"ray_tpu/serve/mod.py": above},
                  rules=["bounded-blocking"])
    assert not r.findings and len(r.suppressed) == 1

    wrong = above.replace("disable=bounded-blocking",
                          "disable=async-purity")
    r = lint_tree(tmp_path, {"ray_tpu/serve/mod.py": wrong},
                  rules=["bounded-blocking"])
    assert rules_of(r) == ["bounded-blocking"], r.findings


def test_bare_suppression_reported_even_without_finding(tmp_path):
    # a reasonless waiver is a contract violation on its own — it must
    # not hide until some finding happens to land on its line
    src = "x = 1  # raylint: disable=bounded-blocking\n"
    r = lint_tree(tmp_path, {"ray_tpu/mod.py": src},
                  rules=["bounded-blocking"])
    assert rules_of(r) == ["suppression-hygiene"], r.findings

    # and a waiver naming a nonexistent rule is reported despite a reason
    # (literal split so the engine's raw-line scan of THIS file, which
    # is part of the linted tree, does not see a real waiver here)
    src = "x = 1  # ray" "lint: disable=not-a-rule -- well argued\n"
    r = lint_tree(tmp_path, {"ray_tpu/mod.py": src},
                  rules=["bounded-blocking"])
    assert rules_of(r) == ["suppression-hygiene"], r.findings
    assert "unknown rule" in r.findings[0].message


def test_syntax_error_is_a_finding(tmp_path):
    r = lint_tree(tmp_path, {"ray_tpu/broken.py": "def f(:\n"})
    assert [f.rule for f in r.findings] == ["syntax-error"]


def test_unknown_rule_raises(tmp_path):
    with pytest.raises(ValueError, match="unknown rule"):
        lint_tree(tmp_path, {"ray_tpu/x.py": ""}, rules=["no-such-rule"])


def test_explicit_missing_path_raises(tmp_path):
    # a typoed explicit path must be an internal error (CLI exit 2),
    # never a silent 0-file "clean" run
    with pytest.raises(ValueError, match="not found"):
        run_lint(str(tmp_path), paths=["no_such_dir"])
    # the DEFAULT_PATHS set stays best-effort: an empty root is clean
    assert run_lint(str(tmp_path)).files_scanned == 0


# ---------------------------------------------------------------------------
# live-tree gate: the repo must lint clean, rule by rule
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def live_result():
    return run_lint(REPO)


def test_expected_rule_set(live_result):
    # ≥6 checkers active, including every migrated test_tooling guard
    assert set(live_result.rules) >= {
        "thread-lifecycle", "bounded-blocking", "async-purity",
        "lock-discipline", "context-capture", "fault-site-coverage",
        "proxy-request-context", "collective-supervision",
        "serial-blocking-get", "test-hygiene", "bench-emission",
        "sharding-discipline", "gcs-verb-idempotency"}


@pytest.mark.parametrize("rule", sorted(
    set(all_rules()) | {"syntax-error", "suppression-hygiene"}))
def test_live_tree_is_clean(live_result, rule):
    findings = [f for f in live_result.findings if f.rule == rule]
    assert not findings, "\n".join(f.render() for f in findings)


def test_live_tree_suppressions_all_carry_reasons():
    """Independent of the engine's own bookkeeping: scan the raw
    comments, so this cannot pass vacuously if the reason-mandatory
    machinery regresses."""
    import re

    pat = re.compile(r"#\s*raylint:\s*disable=[\w\-]+(?:\s*,\s*[\w\-]+)*"
                     r"(?P<reason>\s+--\s*\S.*)?\s*$")
    bad, seen = [], 0
    for top in ("ray_tpu", "tests"):
        for dirpath, dirnames, files in os.walk(os.path.join(REPO, top)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for name in files:
                if not name.endswith(".py"):
                    continue
                path = os.path.join(dirpath, name)
                for i, line in enumerate(open(path, encoding="utf-8"), 1):
                    m = pat.search(line)
                    if m is None:
                        continue
                    seen += 1
                    if not m.group("reason"):
                        bad.append(f"{path}:{i}")
    assert seen >= 10, "suppression scan is broken (found too few)"
    assert not bad, f"reasonless raylint waivers: {bad}"


# ---------------------------------------------------------------------------
# CLI exit-code contract: 0 clean / 1 findings / 2 internal error
# ---------------------------------------------------------------------------

def _cli(args, cwd=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    return subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts.cli", "lint",
         "--format=json"] + args,
        capture_output=True, text=True, env=env, cwd=cwd or REPO,
        timeout=300)


def test_cli_clean_exit_0():
    proc = _cli(["--root", REPO])
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["findings"] == []
    assert payload["files_scanned"] > 100
    assert all(s["suppress_reason"] for s in payload["suppressed"])


def test_cli_findings_exit_1(tmp_path):
    bad = tmp_path / "ray_tpu" / "mod.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("import threading\n\n"
                   "threading.Thread(target=print).start()\n")
    proc = _cli(["--root", str(tmp_path)])
    assert proc.returncode == 1, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert [f["rule"] for f in payload["findings"]] == ["thread-lifecycle"]
    assert payload["findings"][0]["path"] == "ray_tpu/mod.py"


def test_cli_internal_error_exit_2():
    proc = _cli(["--root", REPO, "--rules", "no-such-rule"])
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert "internal error" in proc.stderr
