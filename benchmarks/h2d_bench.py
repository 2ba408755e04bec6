"""Host→HBM staging microbench: prove the zero-copy arena path.

SURVEY §7 hard-part 5 / VERDICT r2 #8: object payloads are written 64-byte
aligned into the shm arena precisely so ``jax.device_put`` can DMA straight
from the mapped segment.  This bench measures three H2D paths for the same
payload:

* ``direct``   — device_put from a plain malloc'd numpy array (ceiling)
* ``arena``    — device_put from a ZERO-COPY numpy view over an arena
                 object (the ``iter_jax_batches`` path after
                 deserialize(zero_copy=True))
* ``copychain``— bytes(view) copy first, then device_put (what a naive
                 store API forces)

arena ≈ direct and copychain < arena proves the copy was eliminated.

    python benchmarks/h2d_bench.py [--mib 64] [--iters 8]
"""

from __future__ import annotations

import argparse
import time

import os, sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ray_tpu._private.bench_emit import emit_final_record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mib", type=int, default=64)
    ap.add_argument("--iters", type=int, default=8)
    args = ap.parse_args()

    import jax
    import numpy as np

    from ray_tpu._private import serialization
    from ray_tpu._private.ids import ObjectID
    from ray_tpu._private.native_store import NativeArenaStore, available

    n = args.mib * 1024 * 1024
    src = np.arange(n // 8, dtype=np.int64)

    def bench(make_host):
        host = make_host()
        d = jax.device_put(host)  # warm compile/alloc
        jax.block_until_ready(d)
        t0 = time.perf_counter()
        for _ in range(args.iters):
            host = make_host()
            d = jax.device_put(host)
            jax.block_until_ready(d)
        dt = (time.perf_counter() - t0) / args.iters
        return args.mib / 1024 / dt  # GiB/s

    out = {"mib": args.mib, "device": str(jax.devices()[0])}

    # ceiling: plain numpy
    out["direct_gib_s"] = round(bench(lambda: src), 3)

    if not available():
        emit_final_record({**out, "error": "native arena unavailable"})
        return
    store = NativeArenaStore("/rtpu_h2d_bench", max(2 * n + (1 << 20),
                                                    1 << 26), create=True)
    try:
        oid = ObjectID(b"h2dbench" + b"\0" * 8)
        store.put(oid, src)
        # zero-copy view over the arena mapping (64B-aligned payload)
        val, _ = store.get(oid)
        assert isinstance(val, np.ndarray) and not val.flags["OWNDATA"]
        align = store.get_buffer(oid) is not None
        out["arena_view_aligned"] = bool(align)
        out["arena_gib_s"] = round(bench(lambda: val), 3)

        buf = store.get_buffer(oid)
        out["copychain_gib_s"] = round(
            bench(lambda: np.frombuffer(bytes(buf), np.uint8)), 3)
        out["arena_vs_direct"] = round(
            out["arena_gib_s"] / out["direct_gib_s"], 3)
        out["arena_vs_copychain"] = round(
            out["arena_gib_s"] / out["copychain_gib_s"], 3)
    finally:
        store.close(unlink_created=True)
    emit_final_record(out)


if __name__ == "__main__":
    main()
