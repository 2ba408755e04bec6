"""XLA collective groups — the accelerator plane (reference: NCCLGroup,
``python/ray/util/collective/collective_group/nccl_collective_group.py``).

Two shapes, mirroring how TPUs are actually driven:

- ``XlaMeshGroup``: one process owns a device mesh (a pod-slice host or the
  whole single-controller mesh).  "Ranks" are devices; ops are jitted
  shard_map collectives over ICI (psum / all_gather / reduce_scatter /
  ppermute).  This is the *_multigpu analogue and the fast path.

- ``XlaDistributedGroup``: rank-per-process over jax.distributed.  Rank 0
  publishes the coordinator address in the internal KV (parity with
  ``NCCLUniqueIDStore``'s named-actor rendezvous); every rank calls
  ``jax.distributed.initialize`` and ops run over the global mesh.
  Requires a jaxlib with cross-process collectives for the platform.
"""

from __future__ import annotations

import time
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.util.collective.collective_group.base_collective_group import (
    BaseGroup,
)
from ray_tpu.util.collective.types import ReduceOp

_JAX_REDUCE = {
    ReduceOp.SUM: jax.lax.psum,
    ReduceOp.MAX: jax.lax.pmax,
    ReduceOp.MIN: jax.lax.pmin,
}


def ensure_cpu_collectives_backend() -> None:
    """Select the gloo implementation for CPU cross-process collectives.

    Must run BEFORE the backend is first touched; harmless on TPU hosts
    (only the cpu client reads the knob).  Shared by every
    jax.distributed entry point in the framework.
    """
    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def ensure_jax_distributed(coordinator_address: str, num_processes: int,
                           process_id: int) -> None:
    """``jax.distributed.initialize`` that tolerates a runtime this
    process ALREADY formed (a JaxTrainer worker joining a collective
    group, or a second group in the same actor).  jax raises two
    different errors for that state — "already initialized" and, once
    any computation touched the backend, "must be called before any JAX
    calls" — both are acceptable ONLY when a distributed client is in
    fact live.  The tolerance is safe by construction: the live world
    is validated against the requested (num_processes, process_id)
    before returning — an inherited runtime under a different rank
    would silently place this host's data at the wrong global rows."""
    ensure_cpu_collectives_backend()
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes, process_id=process_id)
    except RuntimeError as e:
        msg = str(e)
        tolerated = "already" in msg
        if not tolerated and "before any JAX" in msg:
            try:
                from jax._src import distributed as _dist

                tolerated = _dist.global_state.client is not None
            except Exception:  # noqa: BLE001 — private-API drift
                tolerated = False
        if not tolerated:
            raise
    # some PJRT plugins take the client's process count from the device
    # topology and quietly ignore the coordination service — each worker
    # would then train an INDEPENDENT copy with no gradient exchange
    if jax.process_count() != num_processes:
        raise RuntimeError(
            f"jax.distributed formed {jax.process_count()} process(es), "
            f"expected {num_processes}: platform "
            f"{jax.default_backend()!r} did not honor multi-process "
            "initialization on this host")
    if jax.process_index() != process_id:
        raise RuntimeError(
            f"jax.distributed process_index {jax.process_index()} != "
            f"assigned rank {process_id}: this process inherited a "
            "runtime formed under a different rank")


def _shard_map(fn, mesh, in_specs, out_specs):
    # check_vma=False: ops like all_gather produce replicated outputs the
    # varying-axis checker cannot statically infer.
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


class XlaMeshGroup(BaseGroup):
    """Device-collectives over a single-process mesh (axis "x").

    Tensors are jax arrays sharded (or shardable) over the mesh's first
    axis.  Each op compiles once per shape and runs entirely on ICI.
    """

    def __init__(
        self,
        world_size: int,
        rank: int = 0,
        group_name: str = "default",
        *,
        devices: Optional[List[jax.Device]] = None,
    ):
        super().__init__(world_size, rank, group_name)
        devices = devices or jax.devices()[:world_size]
        if len(devices) < world_size:
            raise ValueError(
                f"need {world_size} devices, have {len(devices)}"
            )
        self.mesh = Mesh(np.asarray(devices), ("x",))
        self._sharded = NamedSharding(self.mesh, P("x"))
        self._replicated = NamedSharding(self.mesh, P())

    def _device_put_sharded(self, tensor):
        return jax.device_put(tensor, self._sharded)

    def allreduce(self, tensor, op: ReduceOp = ReduceOp.SUM):
        """tensor: per-device values stacked on dim0 [world, ...] (or any
        array sharded over dim0); returns the reduction, replicated."""
        op = ReduceOp(op)
        x = self._device_put_sharded(tensor)
        if op == ReduceOp.PRODUCT:
            # no pprod primitive: all_gather then reduce locally (correct
            # for zeros/negatives, unlike an exp-sum-log formulation)
            body = lambda t: jnp.prod(
                jax.lax.all_gather(t, "x", axis=0), axis=0)
        else:
            red = _JAX_REDUCE[op]
            body = lambda t: red(t, "x")

        def local(t):
            return body(jnp.squeeze(t, 0))

        return _shard_map(
            local, self.mesh, (P("x"),), P()
        )(x)

    def barrier(self) -> None:
        jax.block_until_ready(self.allreduce(np.zeros((self.world_size, 1))))

    def reduce(self, tensor, dst_rank: int = 0, op: ReduceOp = ReduceOp.SUM):
        return self.allreduce(tensor, op)  # replicated result includes dst

    def broadcast(self, tensor, src_rank: int = 0):
        x = self._device_put_sharded(tensor)

        def local(t):
            # ppermute needs unique (src, dst) pairs, so broadcast as a
            # masked psum: only the source contributes.
            t = jnp.squeeze(t, 0)
            mask = jax.lax.axis_index("x") == src_rank
            return jax.lax.psum(jnp.where(mask, t, jnp.zeros_like(t)), "x")[
                None
            ]

        return _shard_map(local, self.mesh, (P("x"),), P("x"))(x)

    def allgather(self, tensor) -> Any:
        x = self._device_put_sharded(tensor)

        def local(t):
            return jax.lax.all_gather(jnp.squeeze(t, 0), "x")

        return _shard_map(local, self.mesh, (P("x"),), P())(x)

    def reducescatter(self, tensor, op: ReduceOp = ReduceOp.SUM):
        op = ReduceOp(op)
        if op != ReduceOp.SUM:
            raise NotImplementedError("reducescatter supports SUM on XLA")
        x = self._device_put_sharded(tensor)

        def local(t):
            # t: [1, world, ...] local stack element; scatter dim 1.
            return jax.lax.psum_scatter(
                jnp.squeeze(t, 0), "x", scatter_dimension=0, tiled=False
            )[None]

        return _shard_map(local, self.mesh, (P("x"),), P("x"))(x)

    def send(self, tensor, dst_rank: int, tag: int = 0) -> None:
        raise NotImplementedError(
            "point-to-point on the mesh group: use ppermute via permute()"
        )

    def recv(self, shape=None, dtype=None, src_rank: int = 0, tag: int = 0):
        raise NotImplementedError(
            "point-to-point on the mesh group: use ppermute via permute()"
        )

    def permute(self, tensor, perm: List[tuple]):
        """ppermute: perm is [(src_device, dst_device), ...]."""
        x = self._device_put_sharded(tensor)

        def local(t):
            return jax.lax.ppermute(jnp.squeeze(t, 0), "x", perm)[None]

        return _shard_map(local, self.mesh, (P("x"),), P("x"))(x)

    def destroy_group(self) -> None:
        pass


class XlaDistributedGroup(BaseGroup):
    """Rank-per-process group over jax.distributed (multi-host TPU pods).

    Rendezvous: rank 0 reserves a TCP port and publishes
    ``collective/{group}/coordinator`` in the internal KV (parity with the
    reference's ``NCCLUniqueIDStore`` named-actor rendezvous,
    ``nccl_collective_group.py:29``).

    The group's collective mesh takes ONE device per process, so mesh
    axis "x" is exactly the rank axis regardless of how many local
    devices each process holds (a v5e host has 4 chips; a CPU test
    process has ``xla_force_host_platform_device_count``).
    """

    def __init__(
        self, world_size: int, rank: int, group_name: str,
        *, timeout_s: Optional[float] = None,
    ):
        super().__init__(world_size, rank, group_name)
        from ray_tpu.experimental import internal_kv
        from ray_tpu.util.collective.supervision import resolve_timeout
        from ray_tpu.util.fault_injection import fault_point

        self._timeout_s = resolve_timeout(timeout_s)
        self._send_seq: dict = {}
        self._recv_seq: dict = {}
        # jitted collective programs keyed by (op, shape, dtype): a fresh
        # closure per call would miss jax's jit cache (keyed on function
        # identity) and RECOMPILE every op — ~150 ms of pure overhead
        # measured per 4 KiB allreduce on CPU
        self._fn_cache: dict = {}
        # epoch-versioned rendezvous (same scheme as the TCP leader key):
        # a re-formed group can never adopt a dead incarnation's
        # coordinator address
        epoch_key = f"collective/{group_name}/epoch"
        key = f"collective/{group_name}/coordinator"
        if rank == 0:
            import json
            import socket

            from ray_tpu.util.collective.supervision import (
                drop_group_status_keys,
            )

            fault_point("collective.rendezvous")
            raw = internal_kv._internal_kv_get(
                epoch_key.encode(), namespace="collective")
            self.epoch = int(raw or 0) + 1
            # sweep ghost member records of a previous incarnation that
            # died without cleanup (same hygiene as the TCP leader)
            drop_group_status_keys(group_name)
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
            s.close()
            addr = f"127.0.0.1:{port}"
            internal_kv._internal_kv_put(
                epoch_key.encode(), str(self.epoch).encode(),
                namespace="collective")
            internal_kv._internal_kv_put(
                key.encode(),
                json.dumps({"epoch": self.epoch, "addr": addr}).encode(),
                namespace="collective",
            )
        else:
            from ray_tpu.util.collective.supervision import (
                parse_rendezvous_entry,
            )

            deadline = time.monotonic() + self._timeout_s
            addr = None
            self.epoch = 0
            while time.monotonic() < deadline:
                fault_point("collective.rendezvous")
                raw = internal_kv._internal_kv_get(
                    key.encode(), namespace="collective"
                )
                if raw:
                    entry = parse_rendezvous_entry(raw)
                    raw_epoch = internal_kv._internal_kv_get(
                        epoch_key.encode(), namespace="collective")
                    current = int(raw_epoch or entry["epoch"])
                    if entry["epoch"] == current:
                        addr = entry["addr"]
                        self.epoch = entry["epoch"]
                        break
                time.sleep(0.05)
            if addr is None:
                raise TimeoutError(
                    "coordinator address never published for the current "
                    "epoch")
        # tolerates a runtime already formed by this process (a JaxTrainer
        # worker, or an earlier group); the helper validates the live
        # world and rank against this group's declaration
        ensure_jax_distributed(addr, world_size, rank)
        by_proc: dict = {}
        for d in jax.devices():
            by_proc.setdefault(d.process_index, d)
        if len(by_proc) != world_size:
            raise RuntimeError(
                f"jax.distributed formed {len(by_proc)} processes, "
                f"expected {world_size}")
        self._proc_devices = [by_proc[p] for p in sorted(by_proc)]
        self.mesh = Mesh(np.asarray(self._proc_devices), ("x",))

    def _global(self, tensor):
        from jax.experimental import multihost_utils

        # the mesh holds one device per process, so this process's shard
        # is exactly [1, ...] — its rank's row of the global [world, ...]
        return multihost_utils.host_local_array_to_global_array(
            np.asarray(tensor)[None], self.mesh, P("x")
        )

    def allreduce(self, tensor, op: ReduceOp = ReduceOp.SUM):
        op = ReduceOp(op)
        x = self._global(tensor)
        key = ("allreduce", op, x.shape, str(x.dtype))
        fn = self._fn_cache.get(key)
        if fn is None:
            red = _JAX_REDUCE[op]

            def local(t):
                return red(jnp.squeeze(t, 0), "x")

            fn = jax.jit(_shard_map(local, self.mesh, (P("x"),), P()))
            self._fn_cache[key] = fn
        out = fn(x)
        return np.asarray(jax.device_get(out.addressable_data(0)))

    def barrier(self) -> None:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(self.group_name)

    def reduce(self, tensor, dst_rank: int = 0, op: ReduceOp = ReduceOp.SUM):
        return self.allreduce(tensor, op)

    def broadcast(self, tensor, src_rank: int = 0):
        from jax.experimental import multihost_utils

        return multihost_utils.broadcast_one_to_all(
            np.asarray(tensor), is_source=self.rank == src_rank
        )

    def allgather(self, tensor) -> List[Any]:
        from jax.experimental import multihost_utils

        out = multihost_utils.process_allgather(np.asarray(tensor))
        return list(out)

    def reducescatter(self, tensor, op: ReduceOp = ReduceOp.SUM):
        out = self.allreduce(tensor, op)
        chunk = out.shape[0] // self.world_size
        return out[self.rank * chunk:(self.rank + 1) * chunk]

    # -- point-to-point ---------------------------------------------------
    #
    # XLA collectives are symmetric (every mesh participant runs the same
    # program), but the BaseGroup send/recv contract is one-sided — only
    # the source calls send, only the destination calls recv (reference
    # ``collective.py:541,604``).  One-sided p2p is host-staged through
    # the internal KV with per-(src,dst,tag) sequence numbers; in-graph
    # transfers between ranks should use the mesh collectives (ppermute
    # via the jitted program) instead — this path is for small control
    # tensors.

    def _p2p_key(self, src: int, dst: int, tag: int, seq: int) -> bytes:
        return (f"collective/{self.group_name}/p2p/"
                f"{src}>{dst}/{tag}/{seq}").encode()

    def send(self, tensor, dst_rank: int, tag: int = 0) -> None:
        import pickle

        from ray_tpu.experimental import internal_kv

        arr = np.asarray(tensor)
        seq = self._send_seq.get((dst_rank, tag), 0)
        self._send_seq[(dst_rank, tag)] = seq + 1
        internal_kv._internal_kv_put(
            self._p2p_key(self.rank, dst_rank, tag, seq),
            pickle.dumps(arr, protocol=5), namespace="collective")

    def recv(self, shape=None, dtype=None, src_rank: int = 0, tag: int = 0):
        import pickle

        from ray_tpu.experimental import internal_kv

        seq = self._recv_seq.get((src_rank, tag), 0)
        key = self._p2p_key(src_rank, self.rank, tag, seq)
        deadline = time.monotonic() + self._timeout_s
        while time.monotonic() < deadline:
            raw = internal_kv._internal_kv_get(key, namespace="collective")
            if raw is not None:
                # advance the cursor only on success: a timed-out recv
                # that bumped it would permanently shift every later
                # message on this (src, tag) stream
                self._recv_seq[(src_rank, tag)] = seq + 1
                internal_kv._internal_kv_del(key, namespace="collective")
                arr = pickle.loads(raw)
                if shape is not None and tuple(arr.shape) != tuple(shape):
                    raise ValueError(
                        f"recv shape mismatch: got {arr.shape}, "
                        f"expected {tuple(shape)}")
                return arr if dtype is None else arr.astype(dtype, copy=False)
            time.sleep(0.002)
        raise TimeoutError(
            f"recv from rank {src_rank} (tag={tag}, seq={seq}) timed out")

    def destroy_group(self) -> None:
        # purge this group's KV footprint (coordinator key + any
        # unconsumed p2p payloads): a later group REUSING the name would
        # otherwise pick up a previous incarnation's coordinator address
        # or deliver its stale tensors as fresh data.  The epoch COUNTER
        # survives (see drop_group_keys) so a straggler still polling
        # with this incarnation's epoch can never pass the next one's
        # epoch check
        from ray_tpu.util.collective.supervision import drop_group_keys

        drop_group_keys(self.group_name)
        try:
            jax.distributed.shutdown()
        except Exception:
            pass
