"""Head-node process: GCS + head raylet on one event loop.

Process-bootstrap equivalent of the reference's
``python/ray/_private/node.py:1467 start_ray_processes`` head path (GCS server
+ raylet + monitors).  One process hosting both servers keeps the single-host
footprint small; additional raylets join as separate processes
(``raylet_proc.py``), giving the reference's multi-node-on-one-host test
topology.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import os
import sys
import time


# strong refs to fire-and-forget startup tasks (the event loop keeps only
# weak references; an un-referenced task can be garbage-collected mid-await)
_BG_TASKS: list = []


async def _start_client_server(session_dir, gcs, raylet, client_port: int):
    """Start the remote-driver proxy (reference: Ray Client server on the
    head, default port 10001), retrying the bind while a previous session
    releases the port, then publish a routable address in the cluster KV."""
    log = logging.getLogger(__name__)
    try:
        from ray_tpu._private.ids import JobID
        from ray_tpu._private.worker import CoreWorker, WorkerMode
        from ray_tpu.util.client import ClientServer

        proxy_worker = CoreWorker(
            mode=WorkerMode.DRIVER, session_dir=session_dir,
            gcs_addr=gcs.addr, raylet_addr=raylet.addr,
            node_id=raylet.node_id, job_id=JobID.from_int(0))
        proxy_worker.start()
        client_server = ClientServer(proxy_worker)
        deadline = asyncio.get_event_loop().time() + 20.0
        while True:
            try:
                host, bound = await client_server.start(port=client_port)
                break
            except OSError:
                if asyncio.get_event_loop().time() > deadline:
                    # another cluster owns the default port for good (a
                    # shared host): serve from an ephemeral port instead —
                    # drivers discover the address via the KV, not the
                    # port number
                    host, bound = await client_server.start(port=0)
                    break
                await asyncio.sleep(0.5)
        # advertise a ROUTABLE address, never the bind host: a remote
        # driver can't connect to "0.0.0.0".  Derive it from the GCS
        # advertise address (same interface reachability)
        if host in ("0.0.0.0", "::", ""):
            gcs_host = gcs.addr.split(":")[1] if ":" in gcs.addr else ""
            host = gcs_host or "127.0.0.1"
        await gcs.handle_kv_put(
            ns="cluster", key="client_server_addr",
            value=f"{host}:{bound}".encode())
    except Exception:
        log.warning("client server failed to start", exc_info=True)


def main():
    # wall stamps of this process's start-up, for the driver's
    # ``init.start_head.<part>`` spans (node.py:_record_head_parts)
    stamps = [("python", time.time())]  # interpreter up, ray_tpu imported
    parser = argparse.ArgumentParser()
    parser.add_argument("--session-dir", required=True)
    parser.add_argument("--resources", required=True, help="json resource map")
    parser.add_argument("--labels", default="{}")
    parser.add_argument("--port", type=int, default=0)
    args = parser.parse_args()

    logging.basicConfig(
        level=os.environ.get("RAY_TPU_LOG_LEVEL", "INFO"),
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )

    from ray_tpu._private.gcs import GcsServer
    from ray_tpu._private.raylet import Raylet

    stamps.append(("imports", time.time()))
    loop = asyncio.new_event_loop()
    asyncio.set_event_loop(loop)

    gcs = GcsServer(args.session_dir)
    raylet = Raylet(
        args.session_dir,
        gcs_addr="",  # filled in after gcs start
        resources=json.loads(args.resources),
        labels=json.loads(args.labels),
        node_name="head",
    )

    async def _start():
        await gcs.start(port=args.port)
        stamps.append(("gcs", time.time()))
        raylet.gcs_addr = gcs.addr
        raylet.gcs.addr = gcs.addr
        await raylet.start()
        stamps.append(("raylet", time.time()))
        # dashboard on the same loop (reference: dashboard head process);
        # off by RAY_TPU_DASHBOARD=0
        if os.environ.get("RAY_TPU_DASHBOARD", "1") != "0":
            try:
                from ray_tpu.dashboard.app import start_dashboard

                dash_addr = await start_dashboard(
                    gcs, port=int(os.environ.get("RAY_TPU_DASHBOARD_PORT", 0)))
                with open(os.path.join(args.session_dir,
                                       "dashboard_address"), "w") as f:
                    f.write(dash_addr)
            except Exception:
                logging.getLogger(__name__).warning(
                    "dashboard failed to start", exc_info=True)
        # remote-driver client proxy (reference: Ray Client server on the
        # head, default port 10001); RAY_TPU_CLIENT_SERVER_PORT=-1 disables
        client_port = int(os.environ.get("RAY_TPU_CLIENT_SERVER_PORT",
                                         "10001"))
        if client_port >= 0:
            # background: the fixed default port may still be held by a
            # just-killed previous session for a few seconds — retry the
            # bind instead of silently giving up, and don't delay head
            # readiness (the gcs_address file) on it.  The task handle is
            # retained: the loop only weak-refs tasks, and a gc mid-retry
            # would silently abort the startup.
            _BG_TASKS.append(asyncio.ensure_future(
                _start_client_server(args.session_dir, gcs, raylet,
                                     client_port)))

        stamps.append(("services", time.time()))  # dashboard, client proxy
        with open(os.path.join(args.session_dir,
                               "head_startup.json"), "w") as f:
            json.dump(stamps, f)
        # head marker for the driver: address file
        addr_file = os.path.join(args.session_dir, "gcs_address")
        with open(addr_file + ".tmp", "w") as f:
            f.write(gcs.addr)
        os.rename(addr_file + ".tmp", addr_file)

    loop.run_until_complete(_start())
    try:
        loop.run_forever()
    except KeyboardInterrupt:
        pass
    sys.exit(0)


if __name__ == "__main__":
    main()
