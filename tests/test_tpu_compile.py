"""Ahead-of-time compiles of the serving kernels for a described TPU v5e.

Nothing runs: each test lowers a Pallas kernel at the paper target's
widths (llama2-7b: 32 kv heads of 128, 16-slot pages; the W4A8 down/up
projection shapes), at the served cells' four paged-attention programs
(Qwen3-8B / 0.6B and deepseek-coder-6.7b / 1.3b, verify and draft step),
or at the served BVQ drafts' (Qwen3-0.6B: K 1024 and 3072 over
128-column blocks of 256-entry codebooks; llama-68m: K 768;
deepseek-coder-1.3b: K 5504), for a v5e that is described, not attached,
and asserts that the compiled program calls the kernel
(``tpu_custom_call``).  What interpret-mode tests cannot see — a block
shape Mosaic refuses, an operand type the MXU lacks — fails here.  The
topology is described inside a fixture, never at import, so every test
worker collects the same tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.bvq import BVQWeight
from repro.kernels.bvq_matmul import bvq_matmul_pallas
from repro.kernels.paged_attn import paged_decode_attention_pallas
from repro.kernels.w4a8_matmul import w4a8_matmul_pallas

B, W, KVS, G, HD, PAGE, PAGES, MAX_PAGES = 4, 5, 32, 1, 128, 16, 129, 32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip can be written to the persistent
    # cache but never read back here: keep it off for this module
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu / no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


# the served cells' four paged programs: (B, KVS, G, W, table pages)
PROGRAMS = {
    "qwen3-target": (16, 8, 4, 4, 129),
    "qwen3-draft": (16, 8, 2, 1, 129),
    "dscoder-target": (8, 32, 1, 4, 145),
    "dscoder-draft": (8, 16, 1, 1, 145),
}


@pytest.mark.parametrize(
    "variant", ["decode", "verify", "int8", "tree", *PROGRAMS]
)
def test_paged_attention_compiles_for_v5e(one_chip, variant):
    b, kvs, g, w, max_pages = PROGRAMS.get(variant, (B, KVS, G, W, MAX_PAGES))
    s = lambda shape, dtype: _spec(one_chip, shape, dtype)
    pool = s((PAGES, kvs, PAGE, HD), jnp.bfloat16)
    table = s((b, max_pages), jnp.int32)
    lengths = s((b,), jnp.int32)
    q = s((b, w, kvs, g, HD), jnp.bfloat16)
    attend = lambda *a, **kw: paged_decode_attention_pallas(
        *a, interpret=False, **kw
    )
    if variant == "decode" or (variant in PROGRAMS and w == 1):
        text = _compiled_text(
            attend, s((b, kvs, g, HD), jnp.bfloat16), pool, pool, table,
            lengths,
        )
    elif variant in ("verify", *PROGRAMS):
        text = _compiled_text(attend, q, pool, pool, table, lengths)
    elif variant == "int8":
        qpool = s((PAGES, kvs, PAGE, HD), jnp.int8)
        scale = s((PAGES, kvs, PAGE, 1), jnp.float32)
        text = _compiled_text(
            lambda q_, k, v, t, n, ks, vs: attend(
                q_, k, v, t, n, k_scale=ks, v_scale=vs
            ),
            q, qpool, qpool, table, lengths, scale, scale,
        )
    else:
        text = _compiled_text(
            lambda q_, k, v, t, n, m: attend(q_, k, v, t, n, tree_mask=m),
            q, pool, pool, table, lengths, s((b, w, w), jnp.float32),
        )
    assert "%paged_decode_attention_pallas" in text
    assert "tpu_custom_call" in text


def test_w4a8_matmul_compiles_for_v5e(one_chip):
    m, k, n = 8, 4096, 11008
    s = lambda shape, dtype: _spec(one_chip, shape, dtype)
    text = _compiled_text(
        lambda x, w, sx, sw: w4a8_matmul_pallas(x, w, sx, sw, interpret=False),
        s((m, k), jnp.int8), s((k // 2, n), jnp.int8),
        s((m, 1), jnp.float32), s((1, n), jnp.float32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m", [16, 128])
@pytest.mark.parametrize("k,n", [(1024, 3072), (3072, 1024), (768, 3072)])
def test_bvq_matmul_compiles_for_v5e(one_chip, m, k, n):
    v, c, bc = 8, 256, 128
    s = lambda shape, dtype: _spec(one_chip, shape, dtype)
    nb = n // bc
    bw = BVQWeight(
        s((nb, c, v), jnp.int8), s((nb, 1, 1), jnp.float32),
        s((nb, k // v, bc), jnp.int32), (k, n), v,
    )
    text = _compiled_text(
        lambda x, w: bvq_matmul_pallas(x, w, interpret=False),
        s((m, k), jnp.bfloat16), bw,
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m", [16, 256])
def test_bvq_matmul_compiles_for_v5e_at_k_5504(one_chip, m):
    """deepseek-coder-1.3b's down projection (K 5504, N 2048): a draft step,
    and a prefill whose rows go in 224-row tiles beside K 5504."""
    test_bvq_matmul_compiles_for_v5e(one_chip, m, 5504, 2048)
