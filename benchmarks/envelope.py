"""Scalability envelope: the reference's stress matrix scaled to one host.

Reference rows (``release/benchmarks/README.md:9-31`` +
``release/perf_metrics/scalability/single_node.json``): 1M queued tasks,
10k object args, 3k returns, 10k-object ``ray.get``, 100 GiB objects, 40k
actors, PG churn.  This driver runs the same shapes scaled to one host
with pass/fail gates.

    python benchmarks/envelope.py [--quick] [--only SECTION,...]

Sections: queued_tasks, actors, many_objects, task_args, task_returns,
big_object, pg_churn.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ray_tpu._private.bench_emit import emit_final_record


def _timer():
    t0 = time.perf_counter()
    return lambda: time.perf_counter() - t0


def bench_queued_tasks(ray_tpu, n: int) -> dict:
    """Submit ``n`` trivial tasks as fast as possible (they queue far ahead
    of the 1-core execution), then drain them all.  Gates: submission must
    stay O(1) per task and the queue must drain without error."""

    @ray_tpu.remote
    def nop():
        return 1

    el = _timer()
    refs = [nop.remote() for _ in range(n)]
    submit_s = el()
    el = _timer()
    total = sum(ray_tpu.get(refs))
    drain_s = el()
    assert total == n
    return {"n": n, "submit_s": round(submit_s, 2),
            "submit_per_s": round(n / submit_s, 0),
            "drain_s": round(drain_s, 2),
            "end_to_end_per_s": round(n / (submit_s + drain_s), 0)}


def bench_actors(ray_tpu, n: int) -> dict:
    """``n`` live actor processes at once (reference: 40k across a
    cluster; scaled).  Gates: all respond to a ping; creation rate
    recorded."""

    @ray_tpu.remote
    class A:
        def ping(self):
            return os.getpid()

    actors = []
    try:
        el = _timer()
        actors = [A.remote() for _ in range(n)]
        # budget scales with n: worker spawn pays a full interpreter
        # start (~2.4 s, serial on 1 vCPU) per actor
        pids = ray_tpu.get([a.ping.remote() for a in actors],
                           timeout=max(1200, n * 8))
        create_s = el()
        assert len(set(pids)) == n, f"{len(set(pids))} distinct actor procs"
        el = _timer()
        ray_tpu.get([a.ping.remote() for a in actors], timeout=600)
        ping_s = el()
        return {"n": n, "create_s": round(create_s, 1),
                "actors_per_s": round(n / create_s, 1),
                "ping_all_s": round(ping_s, 2)}
    finally:
        # ALWAYS reap: a thousand live actor processes would poison every
        # later section (and the box) on failure
        for a in actors:
            try:
                ray_tpu.kill(a)
            except Exception:  # noqa: BLE001
                pass


def bench_many_objects(ray_tpu, n: int) -> dict:
    """``ray.get`` on ``n`` objects at once (reference single_node:
    10k objects in 24.09 s)."""
    el = _timer()
    refs = [ray_tpu.put(np.full(256, i, np.int64)) for i in range(n)]
    put_s = el()
    el = _timer()
    vals = ray_tpu.get(refs, timeout=600)
    get_s = el()
    assert int(vals[n - 1][0]) == n - 1
    return {"n": n, "put_s": round(put_s, 2), "get_s": round(get_s, 2),
            "get_per_s": round(n / get_s, 0)}


def bench_task_args(ray_tpu, n: int) -> dict:
    """One task taking ``n`` ObjectRef args (reference: 10k args in
    18.76 s)."""

    @ray_tpu.remote
    def consume(*parts):
        return sum(int(p[0]) for p in parts)

    refs = [ray_tpu.put(np.full(8, i, np.int64)) for i in range(n)]
    el = _timer()
    out = ray_tpu.get(consume.remote(*refs), timeout=600)
    run_s = el()
    assert out == n * (n - 1) // 2
    return {"n": n, "s": round(run_s, 2)}


def bench_task_returns(ray_tpu, n: int) -> dict:
    """One task returning ``n`` values (reference: 3k returns in 5.84 s)."""

    @ray_tpu.remote(num_returns=n)
    def produce():
        return list(range(n))

    el = _timer()
    refs = produce.remote()
    vals = ray_tpu.get(refs, timeout=600)
    run_s = el()
    assert vals[-1] == n - 1
    return {"n": n, "s": round(run_s, 2)}


def bench_big_object(ray_tpu, gib: float) -> dict:
    """A multi-GiB object end-to-end — exceeds the arena, lands in
    segments/spill, reads back intact (reference: 100 GiB ray.get)."""
    nbytes = int(gib * 1024**3)
    arr = np.empty(nbytes, np.uint8)
    arr[::4096] = 7  # touch pages; avoid 3 GiB of rand
    el = _timer()
    ref = ray_tpu.put(arr)
    put_s = el()
    del arr
    el = _timer()
    out = ray_tpu.get(ref)
    get_s = el()
    assert out.nbytes == nbytes and int(out[4096]) == 7
    del out
    return {"gib": gib, "put_s": round(put_s, 2),
            "put_gib_s": round(gib / put_s, 2),
            "get_s": round(get_s, 2),
            "get_gib_s": round(gib / get_s, 2)}


def bench_pg_churn(ray_tpu, n: int) -> dict:
    """Create+ready+remove ``n`` placement groups (reference stress:
    1.52 ms create / 1.23 ms remove; nightly many_pgs 13.7 PGs/s)."""
    from ray_tpu.util.placement_group import (placement_group,
                                              remove_placement_group)

    el = _timer()
    for _ in range(n):
        pg = placement_group([{"CPU": 1}])
        ray_tpu.get(pg.ready(), timeout=60)
        remove_placement_group(pg)
    s = el()
    return {"n": n, "s": round(s, 2), "pgs_per_s": round(n / s, 1)}


# full sizes == the reference's single-node envelope
# (release/benchmarks/README.md:27-31: 10k args, 3k returns, 10k-object
# get, 1M queued tasks; 100 GiB object is RAM-bound — 10 GiB here
# proves the same arena->segment->spill path on this 125 GB box)
SECTIONS = {
    "queued_tasks": (bench_queued_tasks, 1_000_000, 10_000),
    "actors": (bench_actors, 1_000, 100),
    "many_objects": (bench_many_objects, 10_000, 2_000),
    "task_args": (bench_task_args, 10_000, 200),
    "task_returns": (bench_task_returns, 3_000, 200),
    "big_object": (bench_big_object, 10.0, 1.0),
    "pg_churn": (bench_pg_churn, 200, 30),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced sizes (CI smoke)")
    ap.add_argument("--only", default="",
                    help="comma-separated section subset")
    args = ap.parse_args()
    only = set(filter(None, args.only.split(",")))

    # a 1k-actor creation burst spawns worker processes serially (~2.4 s
    # interpreter start on this box); callers must wait out the burst
    os.environ.setdefault("RAY_TPU_ACTOR_RESOLVE_TIMEOUT_S", "3600")

    import ray_tpu

    ray_tpu.init(num_cpus=16, num_tpus=0)
    results = {}
    failures = {}
    try:
        for name, (fn, full, quick) in SECTIONS.items():
            if only and name not in only:
                continue
            size = quick if args.quick else full
            t0 = time.perf_counter()
            try:
                results[name] = fn(ray_tpu, size)
                results[name]["wall_s"] = round(
                    time.perf_counter() - t0, 1)
                print(f"[envelope] {name}: {results[name]}",
                      file=sys.stderr)
            except BaseException as e:  # noqa: BLE001 - keep going, report
                failures[name] = repr(e)[:500]
                print(f"[envelope] {name} FAILED: {e!r}", file=sys.stderr)
    finally:
        ray_tpu.shutdown()
    emit_final_record({"benchmark": "scalability_envelope",
                       "results": results, "failures": failures,
                       "quick": args.quick})
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
