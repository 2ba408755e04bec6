"""One run of one cell of the benchmark.

    python3 cells/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; its
configuration is ``cells/configs/<config>.json`` (whose ``family`` names
the model's file, ``cells/families/<family>.py``), its traffic mix
``cells/traffic/<traffic>.json`` (whose ``runner`` names the runner,
``cells/<runner>_runner.py``), and each metric it reports has a reader of
its own, ``cells/end_to_end/<metric>.py`` or
``cells/layer_metrics/<metric>.py``.
With ``--trace 0`` the last line of stdout carries the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics.

This process parses arguments, starts the cluster, sends the load and
reduces the numbers.  It never initialises a JAX backend: the chips belong
to the workers it starts.  Without a TPU it exits non-zero and prints no
result; ``--rehearse`` runs toy shapes on the CPU, says so, and prints its
numbers under ``rehearsal.<name>``, never under a metric's name.
"""

import time

T0 = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORK = os.path.join(ROOT, ".cells_work")
# the contract allows a cold first run 1200 s; a warm one 360 s
TIME_LIMIT_S = 1150


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def reader(kind: str, name: str):
    """The metric's own file, found by the metric's name.  A metric split
    by kind of cell because its cells report different end-to-end metrics
    (``device_idle_pct.train``, ``.steady``) may share the file of its
    stem (``device_idle_pct.py``)."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        path = os.path.join(HERE, kind, name.rsplit(".", 1)[0] + ".py")
    spec = importlib.util.spec_from_file_location(
        f"cells.{kind}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_of(bench, section, workload):
    return [m for m in bench[section]
            if "workloads" not in m or workload in m["workloads"]]


def watch_chip_reach(cluster, chips, out):
    """Harness clock from ``init()`` until the cell's chips are all held
    by some process (their device nodes open), seen from outside."""
    from cells import lifecycle

    nodes = lifecycle.chip_nodes()

    def watch():
        while cluster.session_dir is not None:
            if len(lifecycle.chip_holders(nodes)) >= chips:
                out["s"] = time.time() - cluster.init_wall
                return
            time.sleep(0.25)
    threading.Thread(target=watch, daemon=True).start()


def prepare(workload, seed, seconds, trace, rehearse):
    """The cell's files, the environment every process of the run
    inherits, and the context the runner gets (``cells/tools`` use it
    too)."""
    sys.path.insert(0, ROOT)
    from cells import families

    bench = load_json(ROOT, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"]
                 if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"cells: no workload {workload!r} in "
                         f"BENCHMARK.json")
    config = load_json(HERE, "configs", cell["config"] + ".json")
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    family = families.load(config["family"])
    model, engine = dict(config["model"]), dict(config.get("engine", {}))
    if rehearse:
        toy = load_json(HERE, "rehearse.json")
        print("REHEARSAL on the CPU at toy shapes: not a chip result",
              flush=True)
        model.update(family.TOY_MODEL)
        engine.update(toy["engine"] if engine else {})
        traffic = _merge(traffic, toy["traffic"][traffic["runner"]])
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell['chips']}")

    # everything the run starts inherits these: the benchmark's files on
    # the import path, one compile cache at a fixed place in the checkout
    # that also keeps the small programs (JAX's default leaves out what
    # compiled in under a second, and a cell has dozens of those)
    os.environ["PYTHONPATH"] = ROOT + os.pathsep + os.environ.get(
        "PYTHONPATH", "")
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from cells import lifecycle

    # a traced run's files stay until the cell's next run, for
    # cells/tools/dump_trace.py
    trace_dir = os.path.join(WORK, "trace", workload)
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    return bench, cell, {
        "t0": T0, "cluster": lifecycle.Cluster(cell["chips"], rehearse),
        "config": config, "family": family, "model": model,
        "engine": engine, "traffic": traffic, "seed": seed,
        "seconds": seconds if seconds is not None else bench["run_seconds"],
        "trace": trace, "trace_dir": trace_dir, "rehearse": rehearse,
        "chips": cell["chips"]}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy shapes on the CPU, labelled: a debugging "
                         "aid, never a result")
    args = ap.parse_args()
    bench, cell, ctx = prepare(args.workload, args.seed, args.seconds,
                               args.trace, args.rehearse)
    cluster, traffic, trace_dir = (ctx["cluster"], ctx["traffic"],
                                   ctx["trace_dir"])
    model, engine, config = ctx["model"], ctx["engine"], ctx["config"]

    from cells import flops, trace as trace_mod
    from ray_tpu._private.accelerators import jax_backend_initialized

    def on_alarm(signum, frame):
        raise TimeoutError(f"the run exceeded {TIME_LIMIT_S}s")
    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(TIME_LIMIT_S)

    runner = importlib.import_module(f"cells.{traffic['runner']}_runner")
    reach = {}
    run = None
    failed = True
    try:
        cluster.start()
        if cluster.waited_s > 1:
            print(f"cells: waited {cluster.waited_s:.1f}s for the chips "
                  f"to be free", flush=True)
        if not args.rehearse:
            watch_chip_reach(cluster, cell["chips"], reach)
        run = runner.run(ctx)
        failed = False
    except BaseException as e:  # noqa: BLE001 - every path explains itself
        print(f"cells: run of {args.workload} failed", file=sys.stderr)
        cluster.explain(e)
    finally:
        signal.alarm(0)
        cluster.stop()  # kills, and says so, what outlives shutdown()
    if failed:
        return 1

    checks = dict(run.get("checks") or serve_checks(run, traffic))
    checks["the driving process never initialised a JAX backend"] = \
        not jax_backend_initialized()
    if not args.rehearse:
        checks["ran on the TPU, on the chips the cell asks for"] = (
            run["device"]["platform"] == "tpu"
            and run["device"]["count"] == cell["chips"])
    trace = None
    if args.trace:
        path = trace_mod.find_xplane(trace_dir)
        trace = trace_mod.load(path) if path else None
        if trace and not trace["device"]:
            trace = None  # a CPU rehearsal records no device plane

    trace_window_s = None
    if trace:
        first, last = trace_mod.span(trace)  # first to last device operation
        trace_window_s = (last - first) / 1e9
    rctx = {"run": run, "family": ctx["family"], "model": model,
            "engine": engine, "traffic": traffic, "config": config,
            "chips": cell["chips"],
            "trace": trace, "trace_window_s": trace_window_s,
            "setup_s": run["wall_window"] - T0,
            "chip_reach_s": reach.get("s"),
            "peaks": None if args.rehearse
            else flops.peaks(run["device"]["kind"])}
    section = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in metrics_of(bench, section, args.workload):
        value = reader("layer_metrics" if args.trace else "end_to_end",
                       m["name"])(rctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = dict(run["device"])
    line = {"correct": all(checks.values()), "attempted": run["attempted"],
            "failed": run["failed"], "metrics": metrics, "device": device}
    if args.trace and trace and not args.rehearse:
        device["busy_s"] = trace_mod.busy_s_mean(trace)
        device["window_s"] = trace_window_s
        line["breakdown"] = {
            "device_ops": trace_mod.top_device_ops(trace),
            "idle_gaps": trace_mod.idle_gaps(trace)}
    for what, ok in checks.items():
        print(("cells: ok   " if ok else "cells: FAIL ") + what,
              file=sys.stdout if ok else sys.stderr, flush=True)
    if args.rehearse:
        line["rehearsal"] = True
        line["metrics"] = {"rehearsal." + k: v for k, v in metrics.items()}
    print(json.dumps(line), flush=True)
    return 0


def serve_checks(run, traffic):
    ref, tol = run["reference"], traffic["reference"]
    model_programs = [p for p in run["new_programs"]
                      if any(p.startswith(s) for s in tol["model_programs"])]
    if run["new_programs"]:
        print(f"cells: {len(run['new_programs'])} program(s) compiled "
              f"inside the window: {run['new_programs'][:8]}", flush=True)
    if ref:
        print(f"cells: reference {ref}", flush=True)
    return {
        "answers came back": bool(ref and ref["rows"]),
        "every returned token is the reference's choice to within "
        f"{tol['logit_gap_tol']}": bool(ref) and all(
            r["worst_gap"] <= tol["logit_gap_tol"] for r in ref["rows"]),
        "no model program compiled inside the window": not model_programs,
        "every request due in the window came back before the drain "
        "limit": run["drained"],
    }


def _merge(base, over):
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


if __name__ == "__main__":
    sys.exit(main())
