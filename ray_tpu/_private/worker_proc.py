"""Worker process entrypoint.

Equivalent of the reference's default_worker
(``python/ray/_private/workers/default_worker.py``): connect the CoreWorker to
the local raylet, register into the worker pool, serve tasks until told to
exit.
"""

from __future__ import annotations

import logging
import os
import sys
import time


def main():
    logging.basicConfig(
        level=os.environ.get("RAY_TPU_LOG_LEVEL", "INFO"),
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    # A worker's JAX platform follows its lease, not the environment
    # (accelerators.py — one process per chip): until the raylet binds
    # it to the chips of a TPU lease, JAX in here is the CPU — never a
    # chip some other worker holds a lease for.
    from ray_tpu._private import accelerators

    accelerators.worker_started_at = time.time()
    accelerators.pin_jax_platform("cpu")
    session_dir = os.environ["RAY_TPU_SESSION_DIR"]
    gcs_addr = os.environ["RAY_TPU_GCS_ADDR"]
    raylet_addr = os.environ["RAY_TPU_RAYLET_ADDR"]
    node_id = os.environ["RAY_TPU_NODE_ID"]

    from ray_tpu._private import worker as worker_mod
    from ray_tpu._private.ids import JobID
    from ray_tpu._private.worker import CoreWorker, WorkerMode

    core = CoreWorker(
        mode=WorkerMode.WORKER,
        session_dir=session_dir,
        gcs_addr=gcs_addr,
        raylet_addr=raylet_addr,
        node_id=node_id,
        job_id=JobID.from_int(0),
    )
    core.start()
    worker_mod.global_worker = core

    async def _register():
        return await core.raylet.call(
            "register_worker",
            worker_id=core.worker_id.binary(),
            addr=core.serve_addr,
            pid=os.getpid(),
        )

    ack = core.run_coro(_register(), timeout=30)
    # the node's cluster-epoch incarnation: stamped on this worker's GCS
    # mutations so a fenced zombie node's workers are rejected too
    core.node_incarnation = int((ack or {}).get("incarnation", 0))
    # park the main thread; all work happens on the IO loop + executors
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass
    sys.exit(0)


if __name__ == "__main__":
    main()
