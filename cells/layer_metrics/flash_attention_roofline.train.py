"""Layer: kernels.  The flash kernels' share of their roofline: the least
time the chips could take for the forward, dQ and dK/dV kernels of the
traced steps (the larger of operations / peak FLOP/s and bytes / peak
bytes/s, from shapes) over the summed device time of the kernels' events
in the trace.  At sequence 4096 and head size 128 the kernels do
~1365 operations a byte, far right of the ridge (240): compute bounds."""

from cells import flops, trace

# the train step holds no other Mosaic kernel than the flash three
PATTERN = trace.MOSAIC


def read(ctx):
    tr, t = ctx["trace"], ctx["traffic"]
    if tr is None or ctx["peaks"] is None:
        return None
    seconds, count = trace.op_time_s(tr, PATTERN)
    if not count:
        return None
    steps = t["trace"]["steps"]
    # per chip: the batch is split over the chips
    share = steps / ctx["chips"]
    p = ctx["peaks"]
    ops = flops.flash_flops_per_step(ctx["model"], t["batch"], t["seq"])
    byt = flops.flash_bytes_per_step(ctx["model"], t["batch"], t["seq"])
    least = max(ops / p["bf16_flops_per_s"], byt / p["hbm_bytes_per_s"])
    return 100.0 * least * share / seconds
