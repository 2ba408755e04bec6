"""Layer: kernels.  The latent arm of the paged decode kernel
(``ops/pallas/paged_attention.py``) against its roofline: the bytes one
call has to move (the live cached rows of one attention block once: keys
and values are the same row; the family's ``latent_attention_bytes``) over
the HBM peak, over the kernel's mean device time in the trace.  The kernel
is the Mosaic call whose result is ``[slots, heads, kv_lora_rank]`` (the
grouped expert products are Mosaic calls too, with 2-D results).  About 110
operations a byte (64 heads share a row), left of the ridge (240): memory
bounds."""

from cells import trace
from cells import expert_counters


def read(ctx):
    fam, m, e = ctx["family"], ctx["model"], ctx["engine"]
    if (ctx["trace"] is None or ctx["peaks"] is None
            or not hasattr(fam, "latent_attention_bytes")):
        return None
    shape = f"{e['batch_slots']},{m['num_heads']},{m['kv_lora_rank']}"
    seconds, count = trace.op_time_s(
        ctx["trace"], rf"= \w+\[{shape}\][^=]*custom-call\(.*" + trace.MOSAIC)
    live = expert_counters.live_tokens(ctx)
    if not count or live is None:
        return None
    least = (fam.latent_attention_bytes(m, live)
             / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / count)
