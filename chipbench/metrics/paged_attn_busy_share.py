"""Kernel: device time of the Pallas paged-attention kernel
(``kernels/paged_attn.paged_decode_attention_pallas``, a custom call of
that name in the trace) over the device's busy time in the window, in
percent."""
from chipbench.trace import union_ns

KERNEL = "paged_decode_attention_pallas"


def read(ctx):
    if ctx.trace is None:
        return None
    busy = ctx.trace.busy_ns()
    ops = [e for e in ctx.trace.in_window(ctx.trace.ops) if e.name.startswith(KERNEL)]
    if not ops or busy <= 0:
        return None
    return 100.0 * union_ns(ops, ctx.trace.t0_ns, ctx.trace.t1_ns) / busy
