"""Serving driver: the paper's full pipeline on random weights.

    python -m repro.launch.serve --mode apsd --tokens 64 [--widths paper]

Builds a TLM/DLM pair (``--widths smoke`` by default; ``paper`` is the
published llama2-7b / llama-68m pair), quantizes the TLM to W4A8 with the
LRU rotation, compresses the DLM with BVQ, and decodes with vanilla SD or
APSD.  Greedy decoding is LOSSLESS: the output equals plain autoregressive
decoding of the bf16 TLM quantized model (asserted with --check).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.paper_pair import PAIRS
from repro.core.apsd import APSDConfig
from repro.core.speculative import SDConfig
from repro.models import lm
from repro.runtime.compile_cache import enable_compile_cache
from repro.serving import quantized_lm as qlm
from repro.serving.engine import ServingModel, make_interface, serve_apsd, serve_sd

__all__ = ["build_pair", "greedy_reference", "greedy_reference_margins", "main"]


def build_pair(seed: int = 0, s_max: int = 256, quantize: bool = True,
               widths: str = "smoke"):
    """(target ServingModel, draft ServingModel) of the pair
    ``configs.paper_pair.PAIRS[widths]``, random weights drawn from `seed`.

    quantize=True serves the paper's formats: the target in W4A8 with the
    LRU rotation and the draft BVQ-compressed; quantize=False serves both
    in their model dtype.  The published target is built one layer at a
    time (``init_quantized_lm``) to fit one chip; the smoke target keeps
    the whole-model fold, whose exact weights its tests were written
    against."""
    tcfg, dcfg, bcfg = PAIRS[widths]
    key = jax.random.PRNGKey(seed)
    kt, kd = jax.random.split(key)
    # the draft is a BVQ-compressed clone of a same-vocab small model
    dparams, _ = lm.init_lm(kd, dcfg, tp=1)
    if quantize:
        if widths == "paper":
            tq = qlm.init_quantized_lm(kt, tcfg)
        else:
            tq = qlm.quantize_dense_lm(lm.init_lm(kt, tcfg, tp=1)[0], tcfg)
        target = ServingModel(cfg=tcfg, params=tq, mode="w4a8", s_max=s_max)
        dq = qlm.bvq_compress_lm(dparams, dcfg, bcfg, jax.random.PRNGKey(7))
        draft = ServingModel(cfg=dcfg, params=dq, mode="bvq", s_max=s_max)
    else:
        tparams, _ = lm.init_lm(kt, tcfg, tp=1)
        target = ServingModel(cfg=tcfg, params=tparams, mode="bf16", s_max=s_max)
        draft = ServingModel(cfg=dcfg, params=dparams, mode="bf16", s_max=s_max)
    return target, draft


def greedy_reference_margins(target: ServingModel, prompt, n: int,
                             forced=None):
    """Plain autoregressive decode of the target model over a dense cache:
    ``(tokens (n,), margins (n,), gaps (n,))``.

    Greedy by default: each step feeds back its argmax.  With ``forced``
    (n tokens) each step feeds ``forced[i]`` instead (teacher forcing), so
    the reference scores another path's tokens given that path's own
    prefix.  ``margins[i]`` is the top-1 minus top-2 logit at step i, and
    ``gaps[i]`` how far the fed token's logit sits below the top one (0
    where it is the argmax) — how far a numerically different path would
    have to move to pick it."""
    iface = make_interface(target)
    _, cache = iface.prefill(target.params, prompt[:, :-1])
    cur = prompt[0, -1]
    out, margins, gaps = [], [], []
    for i in range(n):
        lg, cache = iface.extend(target.params, cur.reshape(1, 1), cache)
        row = lg[0, -1].astype(jnp.float32)
        top2 = jax.lax.top_k(row, 2)[0]
        cur = (jnp.argmax(row) if forced is None
               else jnp.asarray(forced[i])).astype(jnp.int32)
        out.append(int(cur))
        margins.append(float(top2[0] - top2[1]))
        gaps.append(float(top2[0] - row[cur]))
    return jnp.asarray(out, jnp.int32), margins, gaps


def greedy_reference(target: ServingModel, prompt, n: int):
    """Plain autoregressive greedy decode of the target model."""
    return greedy_reference_margins(target, prompt, n)[0]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["sd", "apsd", "ad"], default="apsd")
    ap.add_argument("--tokens", type=int, default=48)
    ap.add_argument("--draft-len", type=int, default=4)
    ap.add_argument("--no-quant", action="store_true")
    ap.add_argument("--check", action="store_true", help="assert losslessness")
    ap.add_argument("--widths", choices=sorted(PAIRS), default="smoke",
                    help="model pair: smoke-scale, or the published "
                         "llama2-7b / llama-68m widths")
    args = ap.parse_args(argv)
    enable_compile_cache()

    target, draft = build_pair(quantize=not args.no_quant, widths=args.widths)
    prompt = jnp.asarray([[5, 17, 3, 99]], jnp.int32)
    key = jax.random.PRNGKey(0)

    t0 = time.time()
    if args.mode == "ad":
        toks = greedy_reference(target, prompt, args.tokens)
        stats = None
    elif args.mode == "sd":
        toks, stats = serve_sd(
            key, target, draft, prompt,
            SDConfig(draft_len=args.draft_len, temperature=0.0, max_tokens=args.tokens),
        )
    else:
        toks, stats = serve_apsd(
            key, target, draft, prompt,
            APSDConfig(short_dl=2, long_dl=6, temperature=0.0, max_tokens=args.tokens),
        )
    dt = time.time() - t0
    print(f"mode={args.mode} tokens={len(toks)} wall={dt:.2f}s")
    print("output:", [int(t) for t in toks])
    if stats is not None:
        if hasattr(stats, "acceptance_rate"):
            print(f"acceptance={float(stats.acceptance_rate):.3f}")
        else:
            print(f"rejected_ratio={stats.rejected_ratio:.3f} "
                  f"par_rounds={stats.par_rounds}/{stats.rounds}")
    if args.check and args.mode in ("sd", "apsd"):
        ref = greedy_reference(target, prompt, args.tokens)
        assert bool(jnp.all(ref == toks)), "speculative output != AD reference"
        print("LOSSLESS: speculative output == autoregressive reference")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
