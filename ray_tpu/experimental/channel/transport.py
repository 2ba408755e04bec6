"""Tiered per-edge transport for compiled-graph channels.

The paper's headline is "Compiled-Graph NCCL P2P channels become
TPU-to-TPU DMA".  This module is the channel plane's device-awareness:
every cross-process DAG edge gets a transport **tier**, negotiated ONCE
at compile time from the endpoint actors' placement/device info, and the
payload encoding + read-side landing path follow the tier:

- **Tier A — in-mesh fused** (``TIER_FUSED``): both endpoints live in one
  mesh-holding process and the methods are jit-marked; the edge vanishes
  into one compiled XLA program (``compiled_dag._fuse_jit_runs``) and
  values never leave the device.  No channel exists; the tier is recorded
  for the edge so DAG stats explain where the hops went.
- **Tier B — ICI device P2P** (``TIER_DEVICE``): endpoints hold devices
  on the same mesh/slice.  Device-array payloads move as a *device
  frame*: pickle-5 out-of-band buffers serialized straight into the shm
  segment (one staging copy), and the reader lands them with
  ``jax.device_put`` **straight from the shm memoryview** — on TPU that
  is the host-to-chip DMA leg of the remote copy; between chips of one
  process-local mesh :func:`ici_device_copy` moves the array over ICI
  with the ``ppermute`` ring (SNIPPETS.md [2]'s ``shard_map`` right-
  permute with send/recv semaphores is the Pallas shape of the same op —
  see :func:`_pallas_remote_copy`).  A ``JAX_PLATFORMS=cpu`` emulation
  backend (``RAY_TPU_ICI_EMULATE=1``) runs the identical negotiation +
  framing + alias-guard logic without hardware, so the whole tier is
  tier-1-testable.
- **Tier C — zero-copy host shm** (``TIER_HOST``): the portable path.
  Payloads serialize directly into the segment (``Channel.write_value``,
  no intermediate pickle-buffer copy) and the reader deserializes with
  owned buffers before acking.

**Alias guard (the PR 5 bug class).**  The segment is REUSED: the writer
overwrites it as soon as every reader acks.  CPU-backend ``device_put``
returns a view of the host buffer, so a device frame read must not ack
while such a view is live.  The guard is alias-checked by device
platform (``serialization.device_rebuild_guard``): host-aliasing
backends copy before the put; DMA backends put straight from the view,
``block_until_ready`` (transfer done), then release.  The release itself
is version-guarded — an overwrite while a view was live raises instead
of corrupting silently.

**Degradation ladder.**  Every tier degrades to tier C on failure: a
device-frame encode/decode error flips the transport to ``TIER_HOST``
(sticky, counted in ``stats["degraded"]``), and both encodings share one
wire format (a marker word ahead of the payload) so a degraded writer
never desyncs its readers.  A dead peer surfaces exactly as before the
tiers existed: the channel times out / closes, the compiled DAG's
liveness probe turns that into ``ActorDiedError`` and the channel is
retired with the pipeline (PR 8 semantics preserved).
"""

from __future__ import annotations

import dataclasses
import os
import struct
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ray_tpu._private.accelerators import jax_backend_initialized
from ray_tpu.experimental.channel.shared_memory_channel import (
    Channel,
    ChannelClosedError,
)

TIER_FUSED = "A-fused"
TIER_DEVICE = "B-ici"
TIER_HOST = "C-shm"

#: arm the CPU emulation backend for tier B: same-node cpu-backend
#: endpoints negotiate the device tier so the framing/guard/degradation
#: logic runs under JAX_PLATFORMS=cpu exactly as it would over ICI
ENV_EMULATE_ICI = "RAY_TPU_ICI_EMULATE"

# frame layout: one 64-byte slot ahead of the serialized payload keeps
# the pickle-5 buffer alignment intact; word 0 is the encoding marker
_FRAME_HDR = 64
_MARK_HOST = 0
_MARK_DEVICE = 1


def _emulate_ici() -> bool:
    return os.environ.get(ENV_EMULATE_ICI, "") not in ("", "0", "false")


# ---------------------------------------------------------------------------
# Endpoint placement/device info (gathered once at compile time)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class EndpointInfo:
    """Where one DAG endpoint runs and what devices it holds."""

    node_id: str = ""
    pid: int = 0
    platform: str = "none"       # jax backend, or "none" when jax unused
    slice_name: str = ""         # TPU pod/slice identity ("" off-pod)
    device_ids: Tuple[int, ...] = ()
    process_index: int = 0

    def holds_devices(self) -> bool:
        return self.platform not in ("", "none") and bool(self.device_ids)


def local_endpoint_info() -> EndpointInfo:
    """Probe THIS process, without side effects (see
    ``accelerators.jax_backend_initialized``).  Under the ICI emulation a
    not-yet-initialized cpu process reports platform from the
    environment so negotiation still sees matching endpoints."""
    node_id = ""
    try:
        from ray_tpu._private import worker as worker_mod

        w = worker_mod.global_worker
        if w is not None and getattr(w, "node_id", None) is not None:
            node_id = w.node_id.hex()
    except Exception:  # noqa: BLE001 — no runtime: pid still disambiguates
        pass
    platform, device_ids, process_index = "none", (), 0
    if jax_backend_initialized():
        try:
            import jax

            platform = jax.default_backend()
            device_ids = tuple(d.id for d in jax.local_devices())
            process_index = jax.process_index()
        except Exception:  # noqa: BLE001 — backend init failed: host tier
            platform, device_ids = "none", ()
    elif _emulate_ici() and os.environ.get(
            "JAX_PLATFORMS", "").lower().startswith("cpu"):
        # emulation endpoints may not have touched jax yet; the env names
        # the platform and a synthetic device id keeps holds_devices true
        platform, device_ids = "cpu", (0,)
    from ray_tpu._private.accelerators import TPUAcceleratorManager

    return EndpointInfo(
        node_id=node_id, pid=os.getpid(), platform=platform,
        slice_name=TPUAcceleratorManager.get_current_pod_name() or "",
        device_ids=device_ids, process_index=process_index)


def _probe_endpoint(instance) -> EndpointInfo:
    """``_remote_call`` body: runs inside the actor process."""
    return local_endpoint_info()


def gather_endpoint_info(handles: Sequence[Any], *,
                         timeout: float = 30.0) -> Dict[Any, EndpointInfo]:
    """One ``_remote_call`` round over ``handles`` → actor_id → info.
    A failed probe maps to None (its edges negotiate tier C)."""
    import ray_tpu

    refs = [h._remote_call.remote(_probe_endpoint) for h in handles]
    out: Dict[Any, EndpointInfo] = {}
    for h, ref in zip(handles, refs):
        try:
            out[h._actor_id] = ray_tpu.get(ref, timeout=timeout)
        except Exception:  # noqa: BLE001 — probe failure: portable tier
            out[h._actor_id] = None
    return out


def negotiate(writer: Optional[EndpointInfo],
              reader: Optional[EndpointInfo]) -> str:
    """Pick the tier for one writer→reader edge.

    Rules (compile-time, placement-driven):

    - unknown endpoint (probe failed, no info) → ``TIER_HOST``;
    - same process → ``TIER_FUSED`` (the compiled DAG short-circuits
      same-actor edges; callers only ask for completeness/stats);
    - both endpoints hold accelerator devices on the SAME slice
      (``slice_name`` match, tpu platform) → ``TIER_DEVICE``;
    - emulation armed: both cpu-backend endpoints on one node →
      ``TIER_DEVICE`` (the CPU proxy for the ICI edge);
    - everything else → ``TIER_HOST``.
    """
    if writer is None or reader is None:
        return TIER_HOST
    if writer.pid == reader.pid and writer.node_id == reader.node_id:
        return TIER_FUSED
    if (writer.platform == "tpu" and reader.platform == "tpu"
            and writer.holds_devices() and reader.holds_devices()
            and writer.slice_name and
            writer.slice_name == reader.slice_name):
        return TIER_DEVICE
    if (_emulate_ici() and writer.platform == "cpu"
            and reader.platform == "cpu"
            and writer.node_id == reader.node_id):
        return TIER_DEVICE
    return TIER_HOST


def negotiate_channel(writer: Optional[EndpointInfo],
                      readers: Sequence[Optional[EndpointInfo]]) -> str:
    """One shm channel serves every reader with a single wire encoding,
    so the channel's tier is the weakest of its edges: device frames only
    when EVERY reader negotiates the device tier."""
    tiers = [negotiate(writer, r) for r in readers]
    if not tiers:
        return TIER_HOST
    if all(t == TIER_DEVICE for t in tiers):
        return TIER_DEVICE
    return TIER_HOST


# ---------------------------------------------------------------------------
# Device-payload helpers
# ---------------------------------------------------------------------------


def _is_device_payload(value: Any) -> bool:
    """True when every array leaf is a jax.Array — the device frame's
    precondition.  Raw numpy leaves would come back as zero-copy views of
    the reusable segment with no rebuild hook to guard them, so any numpy
    leaf forces the host encoding."""
    import sys

    if "jax" not in sys.modules:
        return False
    import jax
    import numpy as np

    leaves = jax.tree.leaves(value)
    saw_array = False
    for leaf in leaves:
        if isinstance(leaf, jax.Array):
            saw_array = True
        elif isinstance(leaf, np.ndarray):
            return False
    return saw_array


def ici_device_copy(arr, mesh, axis: str, shift: int = 1):
    """Move ``arr`` one step around the mesh ring over ICI — the
    in-process device leg of tier B, reusing the ``ppermute`` ring that
    ``parallel/pipeline.py`` drives for in-graph pipelining.  On TPU the
    compiled program moves shards chip-to-chip over the interconnect; the
    CPU mesh runs the same program as the emulation backend."""
    import jax

    n = mesh.shape[axis]
    perm = [(i, (i + shift) % n) for i in range(n)]

    def _shift(x):
        return jax.lax.ppermute(x, axis, perm)

    mapped = jax.shard_map(
        _shift, mesh=mesh,
        in_specs=jax.sharding.PartitionSpec(axis),
        out_specs=jax.sharding.PartitionSpec(axis))
    return mapped(arr)


def _pallas_remote_copy(x, *, axis: str = "x"):
    """The Pallas shape of the tier-B chip-to-chip hop (SNIPPETS.md [2]):
    an async remote copy to the right neighbor with send/recv semaphores.
    TPU-only — the caller gates on ``jax.default_backend() == "tpu"``;
    the CPU emulation backend stands in for it everywhere else (same
    negotiation, framing, and alias rules; only the copy engine differs).
    """
    import functools

    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(inp_ref, out_ref, send_sem, recv_sem):
        my_id = jax.lax.axis_index(axis)
        n = jax.lax.axis_size(axis)
        neighbor = jax.lax.rem(my_id + 1, n)
        rdma = pltpu.make_async_remote_copy(
            src_ref=inp_ref, dst_ref=out_ref,
            send_sem=send_sem, recv_sem=recv_sem,
            device_id=(neighbor,),
            device_id_type=pltpu.DeviceIdType.LOGICAL)
        rdma.start()
        rdma.wait()

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.TPUMemorySpace.ANY)],
        out_specs=pl.BlockSpec(memory_space=pltpu.TPUMemorySpace.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA],
        compiler_params=pltpu.TPUCompilerParams(collective_id=0),
    )(x)


# ---------------------------------------------------------------------------
# The per-edge transport
# ---------------------------------------------------------------------------


class EdgeTransport:
    """One DAG edge's data plane: a :class:`Channel` plus the negotiated
    tier.  Picklable (ships inside exec specs); read/write carry the
    tier's encoding and attribute wall time to the ``channel_wait`` step
    bucket.  Drop-in where a bare Channel was used."""

    def __init__(self, channel: Channel, tier: str = TIER_HOST,
                 edge: str = ""):
        self.channel = channel
        self.tier = tier
        self.edge = edge
        self.stats = {"sends": 0, "recvs": 0, "bytes_sent": 0,
                      "write_wait_s": 0.0, "read_wait_s": 0.0,
                      "device_frames": 0, "degraded": 0}

    # -- plumbing parity with Channel --------------------------------------
    @property
    def name(self) -> str:
        return self.channel.name

    def set_reader_slot(self, slot: int) -> "EdgeTransport":
        self.channel.set_reader_slot(slot)
        return self

    def close(self) -> None:
        self.channel.close()

    def destroy(self) -> None:
        self.channel.destroy()

    def __reduce__(self):
        return (_rebuild_transport, (self.channel, self.tier, self.edge))

    def __repr__(self):
        return (f"EdgeTransport({self.edge or self.channel.name}, "
                f"tier={self.tier})")

    # -- data plane ---------------------------------------------------------
    def write(self, value: Any, timeout: Optional[float] = None) -> None:
        t0 = time.perf_counter()
        try:
            if (self.tier == TIER_DEVICE and self.channel.supports_zero_copy
                    and _is_device_payload(value)):
                try:
                    n = self._write_frame(value, _MARK_DEVICE, timeout)
                    self.stats["device_frames"] += 1
                except (ChannelClosedError, ValueError, TimeoutError):
                    raise  # lifecycle/size/deadline: not a tier problem
                except Exception:  # noqa: BLE001 — degrade, don't drop
                    self._degrade("device-frame encode failed")
                    n = self._write_frame(value, _MARK_HOST, timeout)
            elif self.channel.supports_zero_copy:
                n = self._write_frame(value, _MARK_HOST, timeout)
            else:  # native data plane: staged bytes, framed the same way
                n = self._write_frame_staged(value, timeout)
            self.stats["sends"] += 1
            self.stats["bytes_sent"] += n
        finally:
            self.stats["write_wait_s"] += time.perf_counter() - t0

    def read(self, timeout: Optional[float] = None) -> Any:
        from ray_tpu._private import tracing

        t0 = time.perf_counter()
        try:
            if self.channel.supports_zero_copy:
                value = self._read_zero_copy(timeout)
            else:
                payload = self.channel.read_bytes(timeout)
                value = self._decode(memoryview(payload), owned=True)
            self.stats["recvs"] += 1
            return value
        finally:
            dt = time.perf_counter() - t0
            self.stats["read_wait_s"] += dt
            tracing.note_duration("channel_wait", dt)
            self._note_edge(dt)

    def read_borrowed(self, fn, timeout: Optional[float] = None) -> Any:
        """Device-landing read: apply ``fn`` to the value while it still
        *borrows* the channel buffer, then release.  Device arrays land
        with ``device_put`` straight from the shm view — zero host
        copies; on host-aliasing backends they alias the segment for the
        duration of the borrow.  ``fn`` must consume the value (reduce
        it, feed it to a jitted step, copy what it keeps) — retaining it
        past the borrow is exactly the PR 5 aliasing bug.  The borrow is
        version-guarded: an overwrite while ``fn`` runs raises instead of
        corrupting.  jax results of ``fn`` are block_until_ready'd before
        the release so lazy dispatch cannot outlive the buffer."""
        from ray_tpu._private import serialization, tracing

        t0 = time.perf_counter()
        dt = None  # channel-attributed portion: acquire + decode ONLY —
        # fn's compute (and its block_until_ready) is consumer time and
        # must not inflate the channel_wait step bucket
        try:
            if not self.channel.supports_zero_copy:
                value = self.read(timeout)  # attributes its own wait
                return fn(value)
            view, version = self.channel.read_acquire(timeout)
            try:
                marker = struct.unpack_from("<Q", view, 0)[0]
                with serialization.device_rebuild_guard(
                        borrow=(marker == _MARK_DEVICE)) as guard:
                    value, _ = serialization.deserialize(
                        view[_FRAME_HDR:],
                        zero_copy=(marker == _MARK_DEVICE))
                dt = time.perf_counter() - t0
                out = fn(value)
                del value
                for arr in guard.arrays:
                    arr.block_until_ready()
                out = _block_jax(out)
            finally:
                self.channel.read_release(version)
            self.stats["recvs"] += 1
            return out
        finally:
            if dt is None and self.channel.supports_zero_copy:
                dt = time.perf_counter() - t0  # failed before decode
            if dt is not None:
                self.stats["read_wait_s"] += dt
                tracing.note_duration("channel_wait", dt)
                self._note_edge(dt)

    # -- internals ----------------------------------------------------------
    def _note_edge(self, dt: float) -> None:
        # per-edge latency into the health plane's process-local tracker
        # (shipped with StepLedger records): a degrading link shows up
        # as one edge's EWMA drifting off its peers
        try:
            from ray_tpu.util.health import note_edge_latency

            note_edge_latency(self.edge or self.channel.name, dt)
        except Exception:  # noqa: BLE001 — evidence stays best-effort
            pass

    def _degrade(self, why: str) -> None:
        if self.tier != TIER_HOST:
            import logging

            logging.getLogger(__name__).warning(
                "channel %s: %s; edge degrades %s -> %s",
                self.edge or self.channel.name, why, self.tier, TIER_HOST)
            self.tier = TIER_HOST
            self.stats["degraded"] += 1

    def _write_frame(self, value: Any, marker: int,
                     timeout: Optional[float]) -> int:
        from ray_tpu._private import serialization

        core, raw_bufs, _refs, total = serialization.serialize_parts(value)
        buf = self.channel.acquire_write_buffer(_FRAME_HDR + total, timeout)
        struct.pack_into("<Q", buf, 0, marker)
        serialization.write_parts(buf[_FRAME_HDR:], core, raw_bufs)
        self.channel.commit_write(_FRAME_HDR + total)
        return total

    def _write_frame_staged(self, value: Any,
                            timeout: Optional[float]) -> int:
        from ray_tpu._private import serialization

        core, raw_bufs, _refs, total = serialization.serialize_parts(value)
        out = bytearray(_FRAME_HDR + total)
        struct.pack_into("<Q", out, 0, _MARK_HOST)
        serialization.write_parts(
            memoryview(out)[_FRAME_HDR:], core, raw_bufs)
        self.channel.write_bytes(bytes(out), timeout)
        return total

    def _read_zero_copy(self, timeout: Optional[float]) -> Any:
        view, version = self.channel.read_acquire(timeout)
        try:
            return self._decode(view, owned=False)
        finally:
            self.channel.read_release(version)

    def _decode(self, view: memoryview, *, owned: bool) -> Any:
        """Decode one frame.  ``owned`` means the bytes backing ``view``
        belong to us (native read copy) — zero-copy views of them cannot
        be clobbered by buffer reuse."""
        from ray_tpu._private import serialization

        marker = struct.unpack_from("<Q", view, 0)[0]
        payload = view[_FRAME_HDR:]
        if marker == _MARK_DEVICE:
            try:
                # device landing: device_put straight from the shm view
                # (the H2D DMA on TPU), alias-guarded by platform, and
                # block_until_ready before the buffer is released
                with serialization.device_rebuild_guard() as guard:
                    value, _ = serialization.deserialize(
                        payload, zero_copy=True)
                for arr in guard.arrays:
                    arr.block_until_ready()
                return value
            except Exception:  # noqa: BLE001 — decode trouble: host path
                self._degrade("device-frame decode failed")
                # fall through to the owned-copy decode below
        value, _ = serialization.deserialize(payload, zero_copy=owned)
        return value


def _block_jax(out: Any) -> Any:
    """Force any jax computation in ``out`` before a borrow ends (async
    dispatch must not read the borrowed buffer after release)."""
    import sys

    if "jax" in sys.modules:
        import jax

        if any(isinstance(leaf, jax.Array) for leaf in jax.tree.leaves(out)):
            jax.block_until_ready(out)
    return out


def _rebuild_transport(channel: Channel, tier: str, edge: str
                       ) -> EdgeTransport:
    return EdgeTransport(channel, tier, edge)


def make_edge_transport(*, tier: str, edge: str = "",
                        buffer_size: int = 1 << 20,
                        num_readers: int = 1) -> EdgeTransport:
    """Create the writer-side transport for one negotiated edge.  Tiered
    channels force the pure-Python data plane (``native=False``): the
    zero-copy value path and deferred-ack reads need direct segment
    access that the native write entrypoint cannot provide."""
    ch = Channel(buffer_size=buffer_size, num_readers=num_readers,
                 native=False)
    return EdgeTransport(ch, tier, edge)


def attach_edge_transport(transport_or_info, slot: int) -> EdgeTransport:
    """Reader-side attach: reconstruct the transport on its own channel
    handle (each reader owns an ack slot)."""
    tr = transport_or_info
    ch = Channel(tr.channel.name, buffer_size=tr.channel.buffer_size,
                 num_readers=tr.channel.num_readers, _create=False)
    ch.set_reader_slot(slot)
    return EdgeTransport(ch, tr.tier, tr.edge)
