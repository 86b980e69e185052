"""Kernel: the BVQ draft's linears against the chip's memory roofline, in
percent.  The bytes of weights every BVQ linear of one draft step must
read (``step_bytes``: int32 indices, the codebook lane tables, the block
scales; x and y, a few percent at draft rows, are left out, so the bytes
are counted low) times the draft-step executions that lie whole inside the
window (``trace.classify_steps``), over the union time of the
``bvq_matmul`` custom calls (``kernels/bvq_matmul.py``) that run inside
those executions, over the chip's HBM bandwidth.  Prefill calls are left
out.  ``None`` where the trace holds no such call (the CPU, or a draft on
the XLA reference path).

Against the MXU the bound is memory: a linear of M rows makes 2·M·K·N
operations on K·N/2 bytes of indices, 4·M operations a byte (32 at 8
rows, 64 at 16), far under the v5e's 240 bf16 operations a byte.  What
keeps the kernel under this roofline is the decode it redoes on every
call: K·N lane gathers and selects on the VPU, a cost that grows with the
weights and not with M, and that these bytes do not count."""
import bisect

from chipbench.trace import classify_steps, union_ns

KERNEL = "bvq_matmul"


def linear_shapes(m: dict) -> list:
    """(K, N) of each BVQ linear of one layer of the model ``m`` (HF keys)."""
    d, f, h = m["hidden_size"], m["intermediate_size"], m["num_attention_heads"]
    hd = m.get("head_dim") or d // h
    kv = m["num_key_value_heads"] * hd
    return [(d, h * hd), (d, kv), (d, kv), (h * hd, d), (d, f), (d, f), (f, d)]


def step_bytes(config: dict) -> int:
    """HBM bytes of weights the draft's BVQ linears read in one draft step:
    per linear, K/v x N int32 indices, ``N / bc`` lane tables of
    v x ceil(C / bc) x bc f32 entries and a ``bc``-lane f32 scale per
    block."""
    bvq = config["serving"]["bvq"]
    v, c, bc = bvq["vec_dim"], bvq["codebook_size"], bvq["block_cols"]
    m = config["draft"]
    per_layer = 0
    for k, n in linear_shapes(m):
        per_layer += (k // v) * n * 4 + (n // bc) * v * -(-c // bc) * bc * 4 + n * 4
    return m["num_hidden_layers"] * per_layer


def read(ctx):
    tr = ctx.trace
    if tr is None:
        return None
    runs = tr.in_window(tr.modules)
    names = classify_steps(runs, "jit_step")
    if names is None:
        return None
    steps = sorted((m for m in runs if m.name == names["draft"]
                    and tr.t0_ns <= m.start_ns and m.end_ns <= tr.t1_ns),
                   key=lambda m: m.start_ns)
    starts = [m.start_ns for m in steps]

    def inside(e):
        i = bisect.bisect_right(starts, e.start_ns) - 1
        return i >= 0 and e.end_ns <= steps[i].end_ns

    ops = [e for e in tr.in_window(tr.ops) if e.name.startswith(KERNEL) and inside(e)]
    busy = union_ns(ops)
    if not ops or busy <= 0:
        return None
    moved = step_bytes(ctx.model) * len(steps)
    return 100.0 * moved / (busy * 1e-9) / ctx.peaks["hbm_bytes_per_s"]
