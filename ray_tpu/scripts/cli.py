"""`raytpu` command-line interface.

Equivalent of the reference's ``ray`` CLI
(``python/ray/scripts/scripts.py``; ``start`` at ``scripts.py:706``):
start/stop a head node, inspect cluster status, list entities.
Uses argparse instead of click (no extra deps).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _connect(address: str):
    import ray_tpu

    ray_tpu.init(address=address)
    return ray_tpu


def cmd_start(args):
    from ray_tpu._private.node import NodeServices, default_resources

    resources = default_resources(num_cpus=args.num_cpus, num_tpus=args.num_tpus)
    if args.resources:
        resources.update(json.loads(args.resources))
    services = NodeServices()
    addr = services.start_head(resources, json.loads(args.labels or "{}"))
    # Detach: the head runs as its own process group; record for `stop`.
    state = {"gcs_addr": addr, "head_pid": services.head_proc.pid,
             "session_dir": services.session_dir}
    os.makedirs(os.path.expanduser("~/.ray_tpu"), exist_ok=True)
    with open(os.path.expanduser("~/.ray_tpu/head.json"), "w") as f:
        json.dump(state, f)
    import atexit

    atexit.unregister(services.stop)
    services._owns_cluster = False  # keep running after this CLI exits
    print(f"Head started. Address: {addr}")
    print(f"Connect with: ray_tpu.init(address='{addr}')")


def cmd_stop(args):
    path = os.path.expanduser("~/.ray_tpu/head.json")
    if not os.path.exists(path):
        print("No running head found.")
        return
    with open(path) as f:
        state = json.load(f)
    from ray_tpu._private.rpc import RpcClient, run_sync

    async def _down():
        c = RpcClient(state["gcs_addr"])
        try:
            await c.call("shutdown_cluster")
        finally:
            await c.close()

    try:
        run_sync(_down())
        print("Cluster shut down.")
    except Exception as e:  # noqa: BLE001
        print(f"Graceful shutdown failed ({e}); killing pid {state['head_pid']}")
        try:
            os.kill(state["head_pid"], 9)
        except ProcessLookupError:
            pass
    os.unlink(path)


def cmd_status(args):
    ray_tpu = _connect(args.address or _default_address())
    from ray_tpu.util.state import list_nodes

    nodes = list_nodes()
    print("Nodes:")
    fenced = zombies = 0
    for n in nodes:
        mark = n.get("state", "ALIVE" if n["alive"] else "DEAD")
        extra = ""
        if mark == "DRAINING":
            left = (n.get("drain_deadline") or 0) - time.time()
            extra = (f" draining: {n.get('drain_reason') or '<no reason>'}"
                     f" ({max(0.0, left):.0f}s to deadline)")
        elif mark == "DEAD" and n.get("death_reason"):
            extra = f" ({n['death_reason']})"
        health = n.get("health", "HEALTHY")
        if health != "HEALTHY":
            extra += f" health={health}"
        if n.get("fenced"):
            fenced += 1
            extra += " fenced"
        if n.get("zombie"):
            zombies += 1
            extra += " ZOMBIE"
        print(f"  {n['node_id'][:12]} [{mark}] {n['addr']} "
              f"inc={n.get('incarnation', 0)} "
              f"total={n['total']}{extra}")
    if fenced or zombies:
        # a zombie is a dead-declared incarnation still contacting the
        # GCS — fenced off, but worth a human look (split-brain debris)
        print(f"Fencing: {fenced} fenced, {zombies} zombie")
    print("Cluster resources:", ray_tpu.cluster_resources())
    print("Available:", ray_tpu.available_resources())
    try:
        from ray_tpu.util.state import list_collective_groups

        groups = list_collective_groups()
    except Exception:  # noqa: BLE001 — status must render without KV
        groups = []
    if groups:
        print("Collective groups:")
        for g in groups:
            line = (f"  {g['group_name']} [{g['state']}] "
                    f"backend={g['backend']} epoch={g['epoch']} "
                    f"members={g['joined']}/{g['world_size']}")
            if g.get("abort_reason"):
                line += f" abort: {g['abort_reason']}"
            print(line)
            for m in g["members"]:
                inflight = m.get("inflight")
                prog = (f"in-flight {inflight['op']} seq={inflight['seq']}"
                        if inflight else
                        f"idle after seq={m.get('last_done_seq', 0)}")
                print(f"    rank {m['rank']} [{m.get('state')}] "
                      f"node={str(m.get('node_id', ''))[:12]} "
                      f"pid={m.get('pid')} {prog}")
    try:
        from ray_tpu.util.state import list_serve_deployments

        deployments = list_serve_deployments()
    except Exception:  # noqa: BLE001 — status must render without KV
        deployments = []
    if deployments:
        print("Serve deployments:")
        for d in deployments:
            line = (f"  {d['name']} replicas={d.get('num_replicas')}"
                    f"/{d.get('goal')} "
                    f"max_ongoing={d.get('max_ongoing_requests')} "
                    f"max_queued={d.get('max_queued_requests')}")
            if d.get("route"):
                line += f" route={d['route']}"
            ov = d.get("overload") or {}
            if any(ov.values()):
                line += (f" overload: shed={ov.get('shed', 0)} "
                         f"expired={ov.get('expired', 0)} "
                         f"cancelled={ov.get('cancelled', 0)} "
                         f"queued={ov.get('queued', 0)}")
            print(line)
    try:
        from ray_tpu.util.state import list_gangs

        gangs = list_gangs()
    except Exception:  # noqa: BLE001 — status must render without gangs
        gangs = []
    if gangs:
        print("Gangs:")
        for g in gangs:
            line = (f"  {g['gang_id'][:12]}"
                    f"{' ' + g['name'] if g.get('name') else ''}"
                    f" [{g['state']}] priority={g.get('priority', 0)}"
                    f" bundles={g.get('bundle_count')}")
            if g.get("placement"):
                line += f" nodes={sorted({n[:8] for n in g['placement']})}"
            if g.get("claim_nodes"):
                line += (f" claiming={len(g['claim_nodes'])} node(s)"
                         f" (preempting)")
            if g.get("preempted_by"):
                line += f" preempted_by={g['preempted_by'][:8]}"
            if g.get("fate_shared"):
                line += f" fate-shared: {g.get('failure')}"
            print(line)
    try:
        from ray_tpu.util.state import list_slo_verdicts

        verdicts = list_slo_verdicts()
    except Exception:  # noqa: BLE001 — status must render without KV
        verdicts = []
    if verdicts:
        print("SLO verdicts:")
        for v in verdicts:
            tag = f"{v.get('plane')}/{v.get('name')}"
            if v.get("phase"):
                tag += f"/{v['phase']}"
            line = f"  {tag} [{v.get('status')}]"
            for viol in v.get("violations") or []:
                line += (f" {viol.get('metric')}={viol.get('value')} "
                         f"(limit {viol.get('limit')})")
            if v.get("status") == "DEGRADED" and v.get("degraded_reason"):
                line += f" ({v['degraded_reason']})"
            print(line)
    ray_tpu.shutdown()


def cmd_health(args):
    """Node health ladder + straggler/SDC verdicts (the health plane's
    operator view — what ``/api/health`` serves on the dashboard)."""
    ray_tpu = _connect(args.address or _default_address())
    from ray_tpu.util.state import list_node_health

    report = list_node_health()
    if args.json:
        print(json.dumps(report, default=str))
        ray_tpu.shutdown()
        return
    print("Node health:")
    for n in report["nodes"]:
        line = (f"  {n['node_id'][:12]} [{n['state']}] "
                f"health={n['health']}")
        if n.get("health_reason"):
            line += f" ({n['health_reason']})"
        if n.get("hw_confirmed"):
            line += " hw-confirmed"
        print(line)
    verdicts = report.get("verdicts") or []
    if verdicts:
        print("Verdicts:")
        for v in verdicts:
            line = (f"  {v.get('kind')}/{v.get('subject')} "
                    f"[{v.get('health')}]")
            if v.get("reason"):
                line += f" {v['reason']}"
            sig = v.get("signals") or {}
            if sig.get("own_time_z") is not None:
                line += f" z={sig['own_time_z']:.1f}"
            if sig.get("probe_ratio") is not None:
                line += f" probe={sig['probe_ratio']:.1f}x"
            if v.get("hw_confirmed"):
                line += " hw-confirmed"
            print(line)
    else:
        print("Verdicts: none (no straggler or SDC reports)")
    ray_tpu.shutdown()


def cmd_drain(args):
    """Operator-initiated node drain (reference ``ray drain-node``)."""
    ray_tpu = _connect(args.address or _default_address())
    from ray_tpu.util.state import drain_node

    # accept a node-id prefix, like the listings print
    target = args.node_id
    matches = [n["node_id"] for n in ray_tpu.nodes()
               if n["node_id"].startswith(target)]
    if len(matches) == 1:
        target = matches[0]
    elif len(matches) > 1:
        print(f"ambiguous node id prefix {target!r} "
              f"({len(matches)} matches)")
        ray_tpu.shutdown()
        sys.exit(1)
    ack = drain_node(target, reason=args.reason,
                     deadline_s=args.deadline_s)
    if ack.get("accepted"):
        left = ack["deadline"] - time.time()
        print(f"draining {target[:12]} (deadline in {left:.0f}s, "
              f"{len(ack.get('lease_holders', []))} lease holder(s))")
    else:
        print(f"drain rejected: {ack.get('rejection_reason')}")
    ray_tpu.shutdown()
    sys.exit(0 if ack.get("accepted") else 1)


def cmd_memory(args):
    """Cluster object-ref debugging view (reference ``ray memory``)."""
    ray_tpu = _connect(args.address or _default_address())
    from ray_tpu.util import state as state_api

    summary = state_api.memory_summary()
    if args.json:
        print(json.dumps(summary, default=str))
    else:
        hdr = (f"{'object_id':<32} {'refs':>4} {'borr':>4} {'pins':>4} "
               f"{'cont':>4} {'lin':>3} {'where':<6} size")

        def row(r, indent):
            print(f"{indent}{r['object_id']:<32} {r['local_refs']:>4} "
                  f"{len(r['borrowers']):>4} {r['transfer_pins']:>4} "
                  f"{r['contained_refs']:>4} "
                  f"{'y' if r['has_lineage'] else '-':>3} "
                  f"{r.get('where', '-'):<6} {r.get('size', '')}")

        for drv in summary["drivers"]:
            print(f"driver pid={drv.get('pid')}")
            print("  " + hdr)
            for r in drv["rows"]:
                row(r, "  ")
        for node in summary["nodes"]:
            print(f"node {node['node_id'][:12]} store={node.get('store')}")
            for wrep in node["workers"]:
                kind = (f"actor {wrep['actor_id'][:12]}"
                        if wrep.get("actor_id") else "worker")
                print(f"  {kind} pid={wrep['pid']}")
                if wrep["rows"]:
                    print("    " + hdr)
                for r in wrep["rows"]:
                    row(r, "    ")
    ray_tpu.shutdown()


def cmd_list(args):
    ray_tpu = _connect(args.address or _default_address())
    from ray_tpu.util import state as state_api

    fn = {
        "actors": state_api.list_actors,
        "nodes": state_api.list_nodes,
        "jobs": state_api.list_jobs,
        "placement-groups": state_api.list_placement_groups,
        "gangs": state_api.list_gangs,
        "slices": state_api.get_slice_topology,
    }[args.entity]
    for row in fn():
        print(json.dumps(row, default=str))
    ray_tpu.shutdown()


def cmd_up(args):
    import logging

    logging.basicConfig(level="INFO")
    from ray_tpu.autoscaler.launcher import cluster_up

    state = cluster_up(args.config, no_monitor=args.no_monitor)
    print(json.dumps({"cluster_name": state["cluster_name"],
                      "address": state["gcs_addr"],
                      "head_pid": state["head_pid"],
                      "workers": len(state.get("workers", []))}))


def cmd_down(args):
    import logging

    logging.basicConfig(level="INFO")
    from ray_tpu.autoscaler.launcher import cluster_down

    ok = cluster_down(args.config)
    print("down" if ok else "no such cluster")


def cmd_job(args):
    from ray_tpu.job_submission import JobSubmissionClient

    client = JobSubmissionClient(args.address or _default_address())
    if args.job_command == "submit":
        entry = list(args.entrypoint)
        if entry and entry[0] == "--":  # argparse.REMAINDER keeps the sep
            entry = entry[1:]
        sid = client.submit_job(entrypoint=" ".join(entry),
                                runtime_env=json.loads(args.runtime_env)
                                if args.runtime_env else None)
        print(sid)
        if args.wait:
            status = client.wait_until_finished(sid, timeout=args.timeout)
            print(status.value)
            print(client.get_job_logs(sid), end="")
            if status.value != "SUCCEEDED":
                raise SystemExit(1)
    elif args.job_command == "status":
        print(json.dumps(client.get_job_info(args.submission_id), default=str))
    elif args.job_command == "logs":
        if getattr(args, "follow", False):
            # stream: poll the DELTA (byte offset) until the job
            # terminates (reference: `ray job logs --follow`)
            import time as _time

            seen = 0
            while True:
                delta, seen = client.poll_job_logs(args.submission_id,
                                                   offset=seen)
                if delta:
                    print(delta, end="", flush=True)
                done = client.get_job_status(
                    args.submission_id).is_terminal()
                if done and not delta:
                    break
                if not delta:
                    _time.sleep(0.5)
        else:
            print(client.get_job_logs(args.submission_id), end="")
    elif args.job_command == "stop":
        print(client.stop_job(args.submission_id))
    elif args.job_command == "list":
        for row in client.list_jobs():
            print(json.dumps(row, default=str))


def cmd_dashboard(args):
    path = os.path.expanduser("~/.ray_tpu/head.json")
    if not os.path.exists(path):
        raise SystemExit("No running head found (raytpu start first).")
    with open(path) as f:
        session_dir = json.load(f)["session_dir"]
    addr_file = os.path.join(session_dir, "dashboard_address")
    if not os.path.exists(addr_file):
        raise SystemExit("Dashboard not running (RAY_TPU_DASHBOARD=0?).")
    with open(addr_file) as f:
        print(f.read().strip())


def cmd_timeline(args):
    ray_tpu = _connect(args.address or _default_address())
    from ray_tpu.util import state as state_api

    events = state_api.timeline(args.output)
    # per-trace summary: one causal tree per request/step (the span layer
    # of docs/observability.md) — how many connected trees the export
    # holds and how big each is, so `raytpu timeline` answers "did my
    # request/step form ONE trace" without opening the viewer
    traces = {}
    for e in events:
        tid = (e.get("args") or {}).get("trace_id")
        if tid:
            traces[tid] = traces.get(tid, 0) + 1
    print(f"Wrote {len(events)} events to {args.output}")
    if traces:
        top = sorted(traces.items(), key=lambda kv: -kv[1])[:8]
        print(f"{len(traces)} trace(s); largest: "
              + ", ".join(f"{t[:8]}…×{n}" for t, n in top))
    ray_tpu.shutdown()


def cmd_lint(args):
    """raylint: AST static analysis over the repo (docs/static_analysis.md).

    Exit-code contract: 0 clean, 1 unsuppressed findings, 2 internal
    error (unknown rule, unreadable tree, checker crash).
    """
    try:
        from ray_tpu._private.analysis import run_lint

        root = args.root
        if root is None:
            # default: the tree containing the installed ray_tpu package
            import ray_tpu

            root = os.path.dirname(os.path.dirname(
                os.path.abspath(ray_tpu.__file__)))
        result = run_lint(root, paths=args.paths or None,
                          rules=args.rules.split(",") if args.rules
                          else None)
    except Exception as e:  # noqa: BLE001 — contract: internal error -> 2
        print(f"raylint: internal error: {type(e).__name__}: {e}",
              file=sys.stderr)
        sys.exit(2)
    if args.format == "json":
        print(result.to_json())
    else:
        print(result.render_human())
    sys.exit(0 if result.clean else 1)


def _default_address() -> str:
    if os.environ.get("RAY_TPU_ADDRESS"):
        return os.environ["RAY_TPU_ADDRESS"]
    path = os.path.expanduser("~/.ray_tpu/head.json")
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)["gcs_addr"]
    raise SystemExit("No address given and no running head found (raytpu start first).")


def main(argv=None):
    parser = argparse.ArgumentParser(prog="raytpu",
                                     description="TPU-native distributed runtime CLI")
    sub = parser.add_subparsers(dest="command", required=True)

    p = sub.add_parser("start", help="start a head node on this machine")
    p.add_argument("--num-cpus", type=float, default=None)
    p.add_argument("--num-tpus", type=float, default=None)
    p.add_argument("--resources", default="")
    p.add_argument("--labels", default="")
    p.set_defaults(fn=cmd_start)

    p = sub.add_parser("stop", help="stop the head started on this machine")
    p.set_defaults(fn=cmd_stop)

    p = sub.add_parser("up", help="launch a cluster from a YAML config")
    p.add_argument("config", help="cluster yaml/json")
    p.add_argument("--no-monitor", action="store_true",
                   help="skip the autoscaling monitor process")
    p.set_defaults(fn=cmd_up)

    p = sub.add_parser("down", help="tear down a launched cluster")
    p.add_argument("config", help="cluster yaml/json or cluster name")
    p.set_defaults(fn=cmd_down)

    p = sub.add_parser("status", help="show cluster nodes and resources")
    p.add_argument("--address", default=None)
    p.set_defaults(fn=cmd_status)

    p = sub.add_parser("health", help="node health ladder and "
                                      "straggler/SDC verdicts")
    p.add_argument("--address", default=None)
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_health)

    p = sub.add_parser("drain", help="drain a node (advance-notice "
                                     "preemption: checkpoint/migrate, "
                                     "then terminate at the deadline)")
    p.add_argument("node_id", help="node id (or unique prefix)")
    p.add_argument("--reason", default="operator drain")
    p.add_argument("--deadline-s", dest="deadline_s", type=float,
                   default=None,
                   help="seconds until the node is terminated "
                        "(default: node_drain_deadline_s config)")
    p.add_argument("--address", default=None)
    p.set_defaults(fn=cmd_drain)

    p = sub.add_parser("list", help="list cluster entities")
    p.add_argument("entity", choices=["actors", "nodes", "jobs",
                                      "placement-groups", "gangs",
                                      "slices"])
    p.add_argument("--address", default=None)
    p.set_defaults(fn=cmd_list)

    p = sub.add_parser("memory", help="object-ref debugging view "
                                      "(per-process refcount tables)")
    p.add_argument("--address", default=None)
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_memory)

    p = sub.add_parser("job", help="submit and manage jobs")
    jsub = p.add_subparsers(dest="job_command", required=True)
    ps = jsub.add_parser("submit")
    ps.add_argument("entrypoint", nargs=argparse.REMAINDER,
                    help="-- shell command to run")
    ps.add_argument("--runtime-env", default=None, help="json runtime env")
    ps.add_argument("--wait", action="store_true",
                    help="block until finished, print logs")
    ps.add_argument("--timeout", type=float, default=600.0)
    for name in ("status", "logs", "stop"):
        pj = jsub.add_parser(name)
        pj.add_argument("submission_id")
        if name == "logs":
            pj.add_argument("--follow", action="store_true",
                            help="stream logs until the job terminates")
    jsub.add_parser("list")
    p.add_argument("--address", default=None)
    p.set_defaults(fn=cmd_job)

    p = sub.add_parser("dashboard", help="print the dashboard URL")
    p.set_defaults(fn=cmd_dashboard)

    p = sub.add_parser("lint", help="run the raylint static-analysis "
                                    "suite (0 clean / 1 findings / "
                                    "2 internal error)")
    p.add_argument("paths", nargs="*",
                   help="files/dirs to scan, relative to --root "
                        "(default: ray_tpu tests benchmarks)")
    p.add_argument("--root", default=None,
                   help="repo root (default: the tree containing the "
                        "ray_tpu package)")
    p.add_argument("--rules", default=None,
                   help="comma-separated rule ids (default: all)")
    p.add_argument("--format", choices=["human", "json"], default="human")
    p.set_defaults(fn=cmd_lint)

    p = sub.add_parser("timeline", help="export chrome://tracing timeline")
    p.add_argument("--output", default="timeline.json")
    p.add_argument("--address", default=None)
    p.set_defaults(fn=cmd_timeline)

    args = parser.parse_args(argv)
    args.fn(args)


if __name__ == "__main__":
    main()
