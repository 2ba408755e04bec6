"""Layer: kernels.  The flash forward kernel with a window
(``ops/pallas/flash_attention.py``) in the traced prefills against its
roofline: what the traced prompts ASK of it (the family's
``flash_prefill_flops`` / ``flash_prefill_bytes`` at each prompt's true
length, ``prompt_tokens`` of its ``engine.admit`` span: only the (query,
key) pairs the causal mask and a layer's window admit) as the larger of
operations over the bf16 peak and bytes over the HBM peak, over the device
time of the kernel's calls.  The kernel is the Mosaic call whose first
result is ``[heads, padded prompt, head_dim]``, one call a layer and
prefill.  A bucket's padding and the blocks that run whole under a mask
are in the time and not in the yield, so the share falls with both.

Spans and calls are matched by bucket: a bucket's calls in the trace, as
prefills (calls over layers), times the mean yield of the prompts admitted
into that bucket in the trace; a bucket whose admission lies outside the
trace is left out, time and yield alike."""

import re

from cells import spans, trace


def read(ctx):
    fam, m, tr = ctx["family"], ctx["model"], ctx["trace"]
    if tr is None or ctx["peaks"] is None \
            or not hasattr(fam, "flash_prefill_flops"):
        return None
    prompts = {}  # bucket -> [prompt_tokens]
    for e in spans.named(spans.of_run(ctx) or {}, "engine.admit",
                         kind="full"):
        if "bucket" in e[3]:
            prompts.setdefault(e[3]["bucket"], []).append(
                e[3]["prompt_tokens"])
    rx = re.compile(rf"= \(\w+\[{m['num_heads']},(\d+),{m['head_dim']}\]"
                    rf"[^=]*custom-call\(.*" + trace.MOSAIC)
    calls = {}  # padded prompt -> [seconds, count]
    for c in tr["device"]:
        for name, _, dur in trace.ops(tr, c):
            hit = rx.search(name)
            if hit:
                row = calls.setdefault(int(hit.group(1)), [0.0, 0])
                row[0] += dur / 1e9
                row[1] += 1
    p = ctx["peaks"]
    seconds = least = 0.0
    for padded, (secs, n) in calls.items():
        # the kernel pads a bucket to whole blocks: the largest bucket
        # that fits is the one it ran
        bucket = max((b for b in prompts if b <= padded), default=None)
        if bucket is None:
            continue
        asked = [max(fam.flash_prefill_flops(m, t) / p["bf16_flops_per_s"],
                     fam.flash_prefill_bytes(m, t) / p["hbm_bytes_per_s"])
                 for t in prompts[bucket]]
        least += n / m["num_layers"] * sum(asked) / len(asked)
        seconds += secs
    return 100.0 * least / seconds if seconds else None
