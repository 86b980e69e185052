#!/usr/bin/env python
"""Serve the paper's TLM/DLM pair at published widths on one TPU chip.

    python chip_smoke.py [--seed 0]

One process, one chip.  It fails before building anything unless JAX's
first device is a TPU (it never sets ``JAX_PLATFORMS``), then:

1. builds llama2-7b in W4A8 with the LRU rotation (one layer at a time) and
   llama-68m in BVQ, random weights drawn from ``--seed``;
2. checks that every linear of the BVQ draft resolved to the Pallas kernel
   (``ops.resolve_bvq_impl``), that ``ops.bvq_linear`` returns the kernel's
   output, and that the kernel equals a float64 decode of the weight within
   ``BVQ_TOL`` of the output's largest entry,
   for layer 0 of llama-68m's own linears and for random INT4 codebooks at
   the Qwen3-0.6B draft's shapes, at a draft step (16 bf16 rows) and at 40
   f32 rows;
3. runs ``Engine.run`` on four greedy prompts (two prompt lengths, so two
   prefill compiles) with the default ``EngineConfig`` (chain drafting,
   ``par_mode="off"``), and checks that the paged attention resolved to
   the Pallas kernel and that the compiled verify step calls it;
4. decodes each prompt with ``launch/serve.greedy_reference_margins`` over
   a dense cache: the served tokens must equal the greedy reference up to
   their first divergence, which is tolerated only where the reference's
   top-1 minus top-2 logit margin is below ``LOGIT_TOL``, and, fed the
   served tokens, the reference must rank each within ``LOGIT_TOL`` of its
   top logit;
5. starts ``CompletionServer`` over ``AsyncEngine(Engine(...))`` on a free
   localhost port and sends the four prompts concurrently through
   ``serving/http_client.py``, two streamed and two not; every response
   must be HTTP 200, finish with "length" after ``max_tokens`` tokens, and
   carry exactly the ``Engine.run`` tokens.

Any failed check raises, so the exit code is non-zero.  Earlier lines print
set-up facts (device kind, build and first-run seconds, peak device bytes,
acceptance); the last line is the JSON result
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from pathlib import Path

MAX_TOKENS = 32
PROMPT_LENGTHS = (16, 16, 24, 24)  # two prefill shapes, two compiles
S_MAX = 256  # per-request cache bound: prompt + MAX_TOKENS + draft window
# Served tokens are checked against a dense-cache reference decode of the
# same weights.  On the chip, two dense references (a full forward and a
# step decode) already disagree on the argmax at 9 to 16 of 32 steps: in
# bf16 with int8 activations, this random 32-layer model carries rounding
# differences up to about 0.8 logits, where its top-2 gap is about 0.2.
# A wrong token drawn at random sits about 4 logits below the top (the max
# of 32000 unit-spread logits), so 1.0 separates rounding from faults.
LOGIT_TOL = 1.0
# The BVQ kernel's products are exact (INT4 codebook entries times bf16
# parts of x) and its sums f32, so against a float64 decode its error is
# f32 rounding, about 1e-7 of the output's largest entry; a wrong decode
# or a misplaced tile reads order 1.
BVQ_TOL = 1e-5
# (K, N) of the Qwen3-0.6B draft's linears: wq, wk/wv, wo, w_gate/w_up,
# w_down, served in 128-column blocks of 256-entry, 8-wide codebooks
QWEN3_DRAFT_LINEARS = ((1024, 2048), (1024, 1024), (2048, 1024),
                       (1024, 3072), (3072, 1024))


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _prompts(seed: int, vocab: int):
    import numpy as np

    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, size=n).astype(np.int32)
            for n in PROMPT_LENGTHS]


def _verify_calls_kernel(engine) -> bool:
    """Lower the engine's target step at its verify width from abstract
    arguments: the text holds ``tpu_custom_call`` only where the paged
    kernel lowered for the chip (interpret mode lowers to plain ops)."""
    import jax
    import jax.numpy as jnp

    spec = lambda t: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t
    )
    cfg = engine.cfg
    tables = engine._t_tables
    lowered = engine._t_step.lower(
        spec(engine.target.params),
        jax.ShapeDtypeStruct((cfg.max_batch, cfg.max_dl + 1), jnp.int32),
        spec(engine._t_store["none"]),
        jax.ShapeDtypeStruct(tables.table.shape, jnp.int32),
        jax.ShapeDtypeStruct(tables.lengths.shape, jnp.int32),
    )
    return "tpu_custom_call" in lowered.as_text()


def _bvq_dense64(bw):
    """The (K, N) weight of a BVQ weight, decoded on the host in float64."""
    import numpy as np

    nb, rows, bc = bw.indices.shape
    cb = (np.asarray(bw.codebooks, np.float64)
          * np.asarray(bw.scales, np.float64))
    w = cb[np.arange(nb)[:, None, None], np.asarray(bw.indices)]
    return w.transpose(1, 3, 0, 2).reshape(bw.shape)


def check_bvq_kernel(draft, seed: int, tol: float):
    """The draft's linears resolved to the Pallas kernel, ``ops.bvq_linear``
    returns the kernel's output, and the kernel (f32 out) equals a float64
    decode at the served shapes.  Returns ``[(linear, rows, dtype, error /
    largest output)]``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.bvq import BVQWeight
    from repro.kernels import ops
    from repro.kernels.bvq_matmul import bvq_matmul_pallas
    from repro.serving import quantized_lm as qlm

    counts = qlm.bvq_impl_counts(draft.params, draft.use_pallas)
    _check(counts["reference"] == 0 and counts["pallas"] > 0,
           f"the BVQ draft's linears resolved to {counts}")
    is_bw = lambda t: isinstance(t, BVQWeight)
    weights = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            draft.params, is_leaf=is_bw)[0]:
        if is_bw(leaf):
            if leaf.indices.ndim == 4:  # stacked over layers: take layer 0
                leaf = jax.tree.map(lambda a: a[0], leaf)
            weights.append((f"{draft.cfg.name}{jax.tree_util.keystr(path)}",
                            leaf))
    rng = np.random.RandomState(seed)
    v, c, bc = 8, 256, 128
    for k, n in QWEN3_DRAFT_LINEARS:
        weights.append((f"qwen3-0.6b-draft({k}x{n})", BVQWeight(
            jnp.asarray(rng.randint(-8, 8, (n // bc, c, v)).astype(np.int8)),
            jnp.asarray(rng.uniform(0.5, 1.5, (n // bc, 1, 1))
                        .astype(np.float32) / np.sqrt(k)),
            jnp.asarray(rng.randint(0, c, (n // bc, k // v, bc))
                        .astype(np.int32)),
            (k, n), v,
        )))
    linear = jax.jit(ops.bvq_linear)
    kernel = jax.jit(bvq_matmul_pallas)  # f32 out, where bvq_linear casts
    report = []
    for name, bw in weights:
        _check(ops.resolve_bvq_impl(bw) == "pallas",
               f"{name}: bvq_linear resolved to the reference")
        w = _bvq_dense64(bw)
        for m, dtype in ((16, jnp.bfloat16), (40, jnp.float32)):
            x = jnp.asarray(rng.randn(m, bw.shape[0]), jnp.float32).astype(dtype)
            y = kernel(x, bw)
            _check(bool(jnp.array_equal(linear(x, bw), y.astype(dtype))),
                   f"{name}: bvq_linear differs from the kernel's output")
            want = np.asarray(x.astype(jnp.float32), np.float64) @ w
            got = np.asarray(y, np.float64)
            err = float(np.abs(got - want).max() / np.abs(want).max())
            _check(err < tol, f"{name}, {m} {jnp.dtype(dtype).name} rows: "
                   f"bvq_linear is {err:.3g} of its output's scale from a "
                   f"float64 decode (tolerance {tol})")
            report.append((name, m, jnp.dtype(dtype).name, err))
    return report


def check_reference(target, prompts, outs, max_tokens: int, tol: float):
    """Served tokens vs the dense-cache reference decode, per prompt.

    Greedy: the served tokens equal the reference's up to their first
    divergence, where the reference's top-2 margin must be below `tol`.
    Teacher-forced: fed the served tokens, the reference must rank each of
    them within `tol` of its top logit at every step.  Returns ``[(equal
    prefix length, margin at the divergence or None, steps where the
    served token is the reference's argmax, largest gap)]``."""
    import jax.numpy as jnp

    from repro.launch.serve import greedy_reference_margins

    report = []
    for i, (prompt, got) in enumerate(zip(prompts, outs)):
        p = jnp.asarray(prompt[None])
        ref, margins, _ = greedy_reference_margins(target, p, max_tokens)
        ref = [int(t) for t in ref]
        div = next((j for j, (a, b) in enumerate(zip(ref, got)) if a != b),
                   None)
        if div is not None:
            _check(margins[div] < tol,
                   f"prompt {i}: served token {got[div]} != reference "
                   f"{ref[div]} at step {div}, where the reference's top-2 "
                   f"margin {margins[div]:.4f} >= {tol}")
        _, _, gaps = greedy_reference_margins(target, p, max_tokens,
                                              forced=got)
        worst = max(range(max_tokens), key=gaps.__getitem__)
        _check(gaps[worst] < tol,
               f"prompt {i}: served token {got[worst]} at step {worst} sits "
               f"{gaps[worst]:.4f} below the reference's top logit "
               f"(tolerance {tol})")
        report.append((
            max_tokens if div is None else div,
            None if div is None else margins[div],
            sum(g == 0.0 for g in gaps),
            gaps[worst],
        ))
    return report


async def serve_and_check(target, draft, prompts, want, max_tokens: int):
    """Answer `prompts` concurrently over HTTP (even indices whole, odd
    ones streamed) and check each response against `want`."""
    from repro.serving import AsyncEngine, CompletionServer, Engine, EngineConfig
    from repro.serving import http_client as hc

    server = CompletionServer(
        AsyncEngine(Engine(target, draft, EngineConfig()),
                    max_queued=len(prompts))
    )
    await server.start(port=0)
    task = asyncio.ensure_future(server.serve_forever())
    try:
        calls = []
        for i, p in enumerate(prompts):
            payload = {"prompt": [int(t) for t in p], "max_tokens": max_tokens}
            calls.append(
                hc.sse_request(server.port, payload) if i % 2
                else hc.request(server.port, "POST", "/v1/completions", payload)
            )
        results = await asyncio.gather(*calls)
    finally:
        task.cancel()
        try:
            await task
        except asyncio.CancelledError:
            pass  # the cancellation requested just above
        await server.stop()
    for i, (res, ref) in enumerate(zip(results, want)):
        status = res[0]
        _check(status == 200, f"request {i}: HTTP {status}")
        if i % 2:
            chunks = res[2]
            toks = [c["token"] for c in chunks if c["token"] is not None]
            reason = chunks[-1]["finish_reason"]
        else:
            body = json.loads(res[2])
            toks, reason = body["token_ids"], body["finish_reason"]
        kind = "streamed" if i % 2 else "whole"
        _check(reason == "length",
               f"request {i} ({kind}): finish_reason {reason!r}")
        _check(len(toks) == max_tokens,
               f"request {i} ({kind}): {len(toks)} tokens, asked {max_tokens}")
        _check(toks == ref,
               f"request {i} ({kind}): tokens differ from Engine.run")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    print(f"device_kind {dev.device_kind}  devices {len(jax.devices())}")

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.serve import build_pair
    from repro.runtime.compile_cache import enable_compile_cache
    from repro.serving import Engine, EngineConfig, SamplingParams
    from repro.serving.api import resolve_paged_attn_impl

    print(f"compile cache {enable_compile_cache()}")

    t0 = time.perf_counter()
    target, draft = build_pair(
        seed=args.seed, s_max=S_MAX, quantize=True, widths="paper"
    )
    jax.block_until_ready((target.params, draft.params))
    stats = dev.memory_stats() or {}
    print(f"build {target.cfg.name} w4a8 + {draft.cfg.name} bvq: "
          f"{time.perf_counter() - t0:.1f} s; peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use')}; bytes_in_use "
          f"{stats.get('bytes_in_use')}")

    t0 = time.perf_counter()
    bvq_report = check_bvq_kernel(draft, args.seed, BVQ_TOL)
    print(f"BVQ kernel: {len(bvq_report)} checks against a float64 decode "
          f"in {time.perf_counter() - t0:.1f} s; largest error "
          f"{max(r[3] for r in bvq_report):.3g} of the output's scale")
    for name, m, dtype, err in bvq_report:
        print(f"  {name} {m}x{dtype}: {err:.3g}")

    prompts = _prompts(args.seed, target.cfg.vocab)
    engine = Engine(target, draft, EngineConfig())
    impl = resolve_paged_attn_impl(engine.target.paged_attn_impl)
    print(f"paged attention impl {impl} (interpret="
          f"{jax.default_backend() == 'cpu'})")
    _check(impl == "pallas", f"paged attention resolved to {impl!r}")
    _check(_verify_calls_kernel(engine),
           "the verify step has no tpu_custom_call: the paged kernel did "
           "not lower for the chip")

    t0 = time.perf_counter()
    outs, summary = engine.run(prompts, SamplingParams(max_tokens=MAX_TOKENS))
    outs = [[int(t) for t in o] for o in outs]
    print(f"Engine.run first call (compiles included): "
          f"{time.perf_counter() - t0:.1f} s; acceptance "
          f"{summary['acceptance_rate']:.3f}; tokens {sum(map(len, outs))}")
    for o in outs:
        _check(len(o) == MAX_TOKENS, f"Engine.run gave {len(o)} tokens")

    report = check_reference(target, prompts, outs, MAX_TOKENS, LOGIT_TOL)
    for i, (n_eq, margin, n_top, gap) in enumerate(report):
        where = ("" if margin is None
                 else f", diverging at a top-2 margin of {margin:.4f}")
        print(f"reference, prompt {i} (len {len(prompts[i])}): greedy "
              f"equal for {n_eq}/{MAX_TOKENS} steps{where}; teacher-forced "
              f"argmax at {n_top}/{MAX_TOKENS} steps, largest gap {gap:.4f}")

    t0 = time.perf_counter()
    asyncio.run(serve_and_check(target, draft, prompts, outs, MAX_TOKENS))
    print(f"HTTP: {len(prompts)} concurrent requests (2 streamed) match "
          f"Engine.run: {time.perf_counter() - t0:.1f} s")

    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
