"""Public jit'd wrappers over the Pallas kernels.

These are the entry points the model/serving layers call; they handle plan
composition (multi-stage rotations), packing, and fall back to the pure-jnp
reference implementations for shapes the kernels do not cover (e.g. channel
dims whose block does not divide them).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import quantization as q
from repro.core import rotation as rot
from repro.core.bvq import BVQWeight
from repro.kernels import ref
from repro.kernels.bvq_matmul import bvq_matmul_pallas, tile_rows
from repro.kernels.fwht import block_rotate_pallas
from repro.kernels.w4a8_matmul import w4a8_matmul_pallas

__all__ = [
    "lru_rotate",
    "lru_rotate_transpose",
    "w4a8_linear",
    "bvq_linear",
    "resolve_bvq_impl",
]


def lru_rotate(
    x: jnp.ndarray, plan: rot.RotationPlan, use_pallas: bool = True
) -> jnp.ndarray:
    """y = x @ R for any RotationPlan, Pallas-kernel backed."""
    apply_block = (
        (lambda t, m, k, tr=False: block_rotate_pallas(t, m, k, transpose=tr))
        if use_pallas
        else (lambda t, m, k, tr=False: rot._apply_blocks(t, m, k, transpose=tr))
    )
    n, b = plan.n, plan.block
    assert x.shape[-1] == n
    if plan.kind == "exact":
        return apply_block(x, plan.m, plan.k)
    if plan.kind == "tiled":
        y = apply_block(x, plan.m, plan.k)
        shift = b // 2
        y = jnp.roll(y, -shift, axis=-1)
        y = apply_block(y, plan.m, plan.k)
        return jnp.roll(y, shift, axis=-1)
    upper = apply_block(x[..., :b], plan.m, plan.k)
    x = jnp.concatenate([upper, x[..., b:]], axis=-1)
    lower = apply_block(x[..., n - b :], plan.m, plan.k)
    return jnp.concatenate([x[..., : n - b], lower], axis=-1)


def lru_rotate_transpose(
    x: jnp.ndarray, plan: rot.RotationPlan, use_pallas: bool = True
) -> jnp.ndarray:
    apply_block = (
        (lambda t, m, k: block_rotate_pallas(t, m, k, transpose=True))
        if use_pallas
        else (lambda t, m, k: rot._apply_blocks(t, m, k, transpose=True))
    )
    n, b = plan.n, plan.block
    assert x.shape[-1] == n
    if plan.kind == "exact":
        return apply_block(x, plan.m, plan.k)
    if plan.kind == "tiled":
        shift = b // 2
        y = jnp.roll(x, -shift, axis=-1)
        y = apply_block(y, plan.m, plan.k)
        y = jnp.roll(y, shift, axis=-1)
        return apply_block(y, plan.m, plan.k)
    lower = apply_block(x[..., n - b :], plan.m, plan.k)
    x = jnp.concatenate([x[..., : n - b], lower], axis=-1)
    upper = apply_block(x[..., :b], plan.m, plan.k)
    return jnp.concatenate([upper, x[..., b:]], axis=-1)


@jax.named_scope("w4a8_linear")
def w4a8_linear(
    x: jnp.ndarray,
    packed_w: jnp.ndarray,  # (K//2, N) int8 nibble-packed
    sw: jnp.ndarray,  # (1, N)
    use_pallas: bool = True,
) -> jnp.ndarray:
    """Dynamic-A8 linear over packed W4 weights: y = Q8(x) @ W4 * sx * sw.
    Its operations carry the named scope ``w4a8_linear`` (op metadata only;
    a profiler trace attributes their device time to it)."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    xq, sx = q.quantize_act_int8(x2)
    if use_pallas:
        y = w4a8_matmul_pallas(xq, packed_w, sx, sw)
    else:
        y = ref.w4a8_matmul_ref2(xq, packed_w, sx, sw)
    return y.reshape(*lead, -1).astype(x.dtype)


def resolve_bvq_impl(
    bw: BVQWeight, use_pallas: Optional[bool] = None, backend: Optional[str] = None
) -> str:
    """``"pallas"`` or ``"reference"``: the path ``bvq_linear`` takes for
    ``bw`` (stacked layer weights too: only the trailing shapes count).

    ``use_pallas`` of ``None`` selects by what the program observes: the
    Pallas kernel on a TPU at a shape it compiles for (128-lane blocks, K
    a whole number of 16-row index tiles, and an M tile of at least 8 rows
    within the kernel's VMEM budget, by ``tile_rows``, which the kernel
    itself takes for every call), the jnp reference everywhere else.  An
    explicit ``True``/``False`` always wins (``True`` runs the kernel
    interpreted off the TPU).  ``backend`` overrides
    ``jax.default_backend()``."""
    if use_pallas is not None:
        return "pallas" if use_pallas else "reference"
    if backend is None:
        backend = jax.default_backend()
    k, _ = bw.shape
    c, v = bw.codebooks.shape[-2:]
    bc = bw.indices.shape[-1]
    fits = (
        bc % 128 == 0
        and k % (16 * v) == 0
        and tile_rows(8, k, bc, v, c) > 0
    )
    return "pallas" if backend == "tpu" and fits else "reference"


@jax.named_scope("bvq_linear")
def bvq_linear(
    x: jnp.ndarray, bw: BVQWeight, use_pallas: Optional[bool] = None
) -> jnp.ndarray:
    """y = x @ reconstruct(bw) with on-the-fly codebook decode, on the path
    ``resolve_bvq_impl`` picks.  The decode and the matmul carry the named
    scope ``bvq_linear``; the kernel's custom call is ``bvq_matmul``."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if resolve_bvq_impl(bw, use_pallas) == "pallas":
        y = bvq_matmul_pallas(x2, bw)
    else:
        y = ref.bvq_matmul_ref2(x2, bw)
    return y.reshape(*lead, -1).astype(x.dtype)
