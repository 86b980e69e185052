"""Continuous-batching serving throughput (the multi-request analogue of the
paper's Fig. 31.1.6 token/s table).

Drives the stepwise ``Engine`` API: aggregate decode throughput at
increasing batch sizes against N sequential single-request drains (a fresh
engine per drain, matching the per-call jit cost every pre-redesign
``serve_sd`` call paid — plus one warm steady-state row for a reused
engine, the state a long-lived server runs in), a page-size sweep of
allocator utilization, and a microbenchmark of the paged-attention kernel
against the gather+dense path it replaces.

`--kv-path` selects the KV residency: `paged` (the Engine's device-resident
pools — prefill scatters into pool pages, decode attends through the page
table, zero host K/V copies) vs `host` (the frozen legacy gather/scatter
loop in serving/host_gather.py kept as the baseline), or `both` to A/B
them.  Per-round K/V copy time is reported separately so the residency win
stays visible: `host` pays O(S_max x B) host traffic per round
(`kv_copy_ms_per_round`), `paged` pays only tiny int32 page-table/length
uploads (`table_upload_ms_per_round`).

`--par-mode` selects the engine's round execution: `off` (two-phase
draft-all-then-verify-all), `wdos` (fused cross-request PAR rounds — the
WDOS planner co-schedules one request's verify with its neighbours' draft
micro-steps in single fused dispatches), or `both` to additionally A/B the
two schedulers on a staggered-admission adaptive workload, recording
rounds-to-drain, fused-slot occupancy, and the modeled-vs-measured overlap
telemetry (the analytic WDOS costs are validated against the fused rounds
that actually ran).

Every run also writes machine-readable ``BENCH_serving.json`` (tokens/s,
rounds, acceptance rate, copy telemetry per configuration) so the perf
trajectory is tracked across PRs — `scripts/ci.sh` runs the smoke variant
and archives the file.  With ``--par-mode both``, ``--trace-out PATH``
additionally records the wdos arm with the span tracer and exports the
staggered round timeline as Chrome-trace JSON (open in
https://ui.perfetto.dev; see docs/OBSERVABILITY.md).

`--spec-mode both` A/Bs tree-structured speculation against single-chain
drafting on a low-acceptance sampled workload: accepted tokens per
request-round, rounds-to-drain, and the greedy bit-identity leg (tree and
chain greedy streams must match token-for-token).

    PYTHONPATH=src python -m benchmarks.bench_serving [--smoke]
        [--kv-path {paged,host,both}] [--paged-attn {auto,gather,pallas}]
        [--par-mode {off,wdos,both}] [--spec-mode {chain,tree,both}]
        [--json PATH] [--trace-out PATH]
"""
import argparse
import dataclasses
import json
import time

import numpy as np

import jax
import jax.numpy as jnp


def _prompts(n, seed=0, vocab=512):
    rng = np.random.RandomState(seed)
    return [
        rng.randint(0, vocab, size=rng.randint(3, 7)).astype(np.int32)
        for _ in range(n)
    ]


def _bench_paged_attn_rows(rows, record):
    from repro.kernels import ref
    from repro.kernels.paged_attn import paged_decode_attention_pallas

    rng = np.random.RandomState(0)
    b, kvs, g, hd, ps, mp = 8, 4, 2, 64, 16, 8
    pool_pages = b * mp
    q = jnp.asarray(rng.randn(b, kvs, g, hd).astype(np.float32))
    kp = jnp.asarray(rng.randn(pool_pages, kvs, ps, hd).astype(np.float32))
    vp = jnp.asarray(rng.randn(pool_pages, kvs, ps, hd).astype(np.float32))
    pt = jnp.asarray(
        rng.permutation(pool_pages).reshape(b, mp).astype(np.int32)
    )
    lens = jnp.asarray(rng.randint(1, ps * mp, size=(b,)).astype(np.int32))

    def timed(fn, n=20):
        fn()  # warm
        t0 = time.perf_counter()
        for _ in range(n):
            jax.block_until_ready(fn())
        return (time.perf_counter() - t0) / n * 1e6

    us_kernel = timed(lambda: paged_decode_attention_pallas(q, kp, vp, pt, lens))
    us_ref = timed(lambda: ref.paged_attn_ref(q, kp, vp, pt, lens))
    backend = jax.default_backend()  # CPU runs the kernel in interpret mode
    rows.append((
        "paged_attn_pallas", us_kernel, f"B={b} pages={mp}x{ps} [{backend}]"
    ))
    rows.append(("paged_attn_gather_ref", us_ref, "gather+dense oracle"))
    # multi-token verify window (the generalization the Engine dispatches)
    w = 4
    qw = jnp.asarray(rng.randn(b, w, kvs, g, hd).astype(np.float32))
    us_win = timed(lambda: paged_decode_attention_pallas(qw, kp, vp, pt, lens))
    rows.append(("paged_attn_pallas_window4", us_win, f"W={w} verify span"))
    record["paged_attn_kernel"] = {
        "backend": backend,
        "pallas_us": us_kernel,
        "gather_ref_us": us_ref,
        "pallas_window4_us": us_win,
    }


def _copy_telemetry(rows, tag, summary):
    """Per-round host K/V copy vs page-table upload time — the residency
    before/after, straight from the engine's instrumentation."""
    rounds = max(summary["rounds"], 1)
    if summary["kv_path"] == "host":
        rows.append((
            f"{tag}_kv_copy_ms_per_round", 0.0,
            f"{summary['kv_copy_s'] / rounds * 1e3:.3f} ms (host gather/scatter)",
        ))
    else:
        rows.append((
            f"{tag}_table_upload_ms_per_round", 0.0,
            f"{summary.get('table_upload_s', 0.0) / rounds * 1e3:.3f} ms "
            "(int32 tables only; zero K/V copies)",
        ))


def _run_paged(target, draft, prompts, bs, max_tokens, page_size=16,
               warm_engine=None, par_mode="off"):
    """One timed drain of the Engine at batch size `bs`.

    A fresh engine per drain re-traces its jitted steps, matching the legacy
    loop's per-call compile cost so the kv-path A/B stays apples-to-apples
    (and stays comparable with this benchmark's historical numbers).  Pass
    ``warm_engine`` to instead measure the steady state a long-lived server
    enjoys — the redesign's reusable jits are exactly what the old
    run-to-drain API could not keep warm."""
    from repro.serving import Engine, EngineConfig, SamplingParams

    sp = SamplingParams(max_tokens=max_tokens)
    if warm_engine is None:
        # size tables to the submitted batch's true peak, like the closed-
        # batch runtime always did — NOT to s_max (the stepwise default for
        # unknown arrivals), so the trajectory stays comparable across PRs
        ml = max(len(p) for p in prompts) + max_tokens + 3
        eng = Engine(target, draft,
                     EngineConfig(max_batch=bs, page_size=page_size,
                                  draft_len=3, max_model_len=ml,
                                  par_mode=par_mode))
    else:
        eng = warm_engine
    t0 = time.perf_counter()
    outs, summary = eng.run(prompts, sp)
    return outs, summary, time.perf_counter() - t0, eng


def _run_host(target, draft, prompts, bs, max_tokens, page_size=16):
    """One timed drain of the frozen legacy host-gather loop (baseline)."""
    from repro.serving.engine import BatchConfig
    from repro.serving.host_gather import serve_batch_host

    cfg = BatchConfig(max_batch=bs, page_size=page_size, max_tokens=max_tokens,
                      draft_len=3, kv_path="host")
    t0 = time.perf_counter()
    outs, summary = serve_batch_host(
        jax.random.PRNGKey(0), target, draft, prompts, cfg
    )
    return outs, summary, time.perf_counter() - t0, None


def _par_ab(target, draft, prompts, max_tokens, rows, record,
            trace_out=None):
    """A/B the two round schedulers on a staggered-admission adaptive
    workload (one request joins per step, short/long windows mixed by the
    per-request controllers): rounds-to-drain and the fused telemetry —
    occupancy (fraction of slots where one request verified WHILE another
    drafted in the same dispatch) plus the modeled overlap the 4-queue WDOS
    claims over in-order issue on exactly the slots that ran, validated
    against the measured serialized slot cost on this backend.

    ``trace_out`` additionally records the wdos arm with a span tracer:
    the exported Chrome-trace JSON holds the engine track's round, slot and
    leaf spans next to the request rows (load it in
    https://ui.perfetto.dev)."""
    from repro.serving import (
        Engine, EngineConfig, SamplingParams, Tracer, validate_chrome_trace,
    )

    short_dl, long_dl = 2, 6
    record["par"] = {}
    for mode in ("off", "wdos"):
        tracer = Tracer() if (trace_out and mode == "wdos") else None
        eng = Engine(target, draft, EngineConfig(
            max_batch=len(prompts), page_size=16,
            adaptive=True, short_dl=short_dl, long_dl=long_dl, par_mode=mode,
        ), trace=tracer)
        t0 = time.perf_counter()
        for p in prompts:
            eng.add_request(p, SamplingParams(max_tokens=max_tokens))
            eng.step()
        while eng.has_unfinished():
            eng.step()
        dt = time.perf_counter() - t0
        summary = eng.summary()
        entry = {
            "rounds_to_drain": summary["rounds"],
            "emitted": summary["emitted"],
            "wall_s": dt,
            "wdos_modeled_speedup": summary["wdos_modeled_speedup"],
        }
        if "fused" in summary:
            entry["fused"] = summary["fused"]
            f = summary["fused"]
            rows.append((
                f"serving_par_{mode}_staggered", 0.0,
                f"{summary['rounds']} rounds; occupancy {f['occupancy']:.2f} "
                f"({f['fused_slots']}/{f['slots']} fused slots); modeled "
                f"overlap {f['modeled_overlap_speedup']:.2f}x vs in-order",
            ))
        else:
            rows.append((
                f"serving_par_{mode}_staggered", 0.0,
                f"{summary['rounds']} rounds (two-phase)",
            ))
        record["par"][mode] = entry
        if tracer is not None:
            trace = tracer.to_chrome_trace()
            problems = validate_chrome_trace(trace)
            assert not problems, f"trace schema violations: {problems[:3]}"
            tracer.export(trace_out)
            n_ev = len(trace["traceEvents"])
            assert n_ev > len(prompts), "trace unexpectedly empty"
            engine_tids = {
                e["tid"] for e in trace["traceEvents"]
                if e.get("ph") == "M" and e.get("args", {}).get("name") == "engine"
            }
            engine_spans = {
                e["name"] for e in trace["traceEvents"]
                if e.get("ph") == "X" and e.get("tid") in engine_tids
            }
            assert "fused_slot" in engine_spans, (
                f"engine track missing fused_slot spans (has {engine_spans})"
            )
            rows.append((
                "serving_wdos_trace", 0.0,
                f"{n_ev} events -> {trace_out} (Perfetto-loadable)",
            ))
            record["par"][mode]["trace"] = {"path": trace_out, "events": n_ev}
    off_r = record["par"]["off"]["rounds_to_drain"]
    wd_r = record["par"]["wdos"]["rounds_to_drain"]
    rows.append((
        "serving_par_rounds_saved", 0.0,
        f"{off_r} -> {wd_r} rounds "
        f"({(1 - wd_r / max(off_r, 1)) * 100:.0f}% fewer, same tokens)",
    ))


def _tree_spec_ab(target, draft, rows, record, arms):
    """A/B chain vs tree speculation on a low-acceptance sampled workload.

    Matched drafting depth (draft_len 3 on both sides); the tree arm
    branches top-2 at EVERY draft step with a budget covering the full
    fan-out (2 + 4 + 8 = 14 nodes), so it hedges each position the chain
    bets on.  The comparable metric is accepted tokens per REQUEST-round
    (engine-step counts are batched across the whole batch and can tie);
    ``scripts/ci.sh`` gates tree >= chain on it.  Each arm also replays a
    greedy wave on its warm engine: greedy tree output must be
    bit-identical to greedy chain output (branching changes rounds, never
    content — the lossless contract from tests/test_tree_spec.py)."""
    from repro.serving import Engine, EngineConfig, SamplingParams

    n_req = 4
    max_tokens = 16
    prompts = _prompts(n_req, seed=3)
    sps = [SamplingParams(temperature=1.5, seed=100 + i, max_tokens=max_tokens)
           for i in range(n_req)]
    configs = {
        "chain": dict(draft_len=3),
        "tree": dict(draft_len=3, spec_mode="tree", tree_budget=14,
                     spec_branches=2, branch_threshold=1.0),
    }
    out = {"arms": {}, "requests": n_req, "max_tokens": max_tokens,
           "temperature": 1.5}
    record["tree_spec"] = out
    greedy_tokens = {}
    for name in arms:
        eng = Engine(target, draft, EngineConfig(
            max_batch=n_req, page_size=8, **configs[name]
        ))
        rids = [eng.add_request(p, sp) for p, sp in zip(prompts, sps)]
        t0 = time.perf_counter()
        while eng.has_unfinished():
            eng.step()
        dt = time.perf_counter() - t0
        reqs = [eng.request(r) for r in rids]
        acc = (sum(r.accepted for r in reqs)
               / max(sum(r.rounds for r in reqs), 1))
        summary = eng.summary()
        # greedy wave on the SAME warm engine (no re-jit): the lossless leg
        outs_g, _ = eng.run(prompts, SamplingParams(max_tokens=max_tokens))
        greedy_tokens[name] = [np.asarray(t) for t in outs_g]
        out["arms"][name] = {
            "accepted_per_request_round": acc,
            "rounds_to_drain": summary["rounds"],
            "emitted": summary["emitted"],
            "wall_s": dt,
        }
        rows.append((
            f"serving_spec_{name}", 0.0,
            f"{acc:.3f} accepted tok/request-round; "
            f"{summary['rounds']} rounds to drain (sampled T=1.5)",
        ))
    if "chain" in out["arms"] and "tree" in out["arms"]:
        for a, b in zip(greedy_tokens["chain"], greedy_tokens["tree"]):
            np.testing.assert_array_equal(
                a, b, err_msg="greedy tree stream != greedy chain stream"
            )
        out["greedy_bit_identical"] = True
        c = out["arms"]["chain"]["accepted_per_request_round"]
        t = out["arms"]["tree"]["accepted_per_request_round"]
        out["accepted_per_round_ratio"] = t / max(c, 1e-9)
        rows.append((
            "serving_spec_tree_ab", 0.0,
            f"{out['accepted_per_round_ratio']:.2f}x accepted/round vs "
            f"chain ({c:.3f} -> {t:.3f}); greedy streams bit-identical",
        ))


def _kv_quant_ab(target, draft, prompts, max_tokens, rows, record, arms,
                 page_size=16):
    """A/B the paged-KV storage precisions at a FIXED pool byte budget.

    The budget is what the fp arm needs to hold the full batch's worst-case
    requests; each arm then gets ``budget // bytes_per_page(arm)`` pages, so
    the int8 arm's ~3.7x smaller pages become ~3.7x more pages — i.e. more
    RESIDENT requests at the same memory, the capacity win compressed KV
    exists for.  Per arm this records bytes/token, max resident requests at
    the budget, acceptance rate, and tokens/s; ``scripts/ci.sh`` gates the
    int8-vs-none acceptance delta at <= 0.05 absolute."""
    from repro.serving import Engine, EngineConfig, SamplingParams
    from repro.serving.paged_cache import pages_for

    n_req = len(prompts)
    ml = max(len(p) for p in prompts) + max_tokens + 3
    pages_per_req = pages_for(ml, page_size)
    out = {"arms": {}, "page_size": page_size, "max_model_len": ml}
    record["kv_quant"] = out
    budget = None
    for arm in arms:
        eng = Engine(target, draft, EngineConfig(
            max_batch=n_req, page_size=page_size, draft_len=3,
            max_model_len=ml, kv_quant=arm,
        ))
        t0 = time.perf_counter()
        outs, summary = eng.run(prompts, SamplingParams(max_tokens=max_tokens))
        dt = time.perf_counter() - t0
        st = summary["target_pool"]
        bpp = int(st.bytes_per_token) * st.page_size
        if budget is None:
            # the FIRST arm (fp when A/Bing) sizes the shared byte budget
            budget = st.num_pages * bpp
            out["pool_budget_bytes"] = budget
        pages_at_budget = budget // bpp
        resident = pages_at_budget // pages_per_req
        tps = sum(int(o.shape[0]) for o in outs) / dt
        out["arms"][arm] = {
            "bytes_per_token": st.bytes_per_token,
            "pages_at_budget": pages_at_budget,
            "max_resident_requests_at_budget": resident,
            "acceptance_rate": summary["acceptance_rate"],
            "tokens_per_s": tps,
            "rounds": summary["rounds"],
        }
        rows.append((
            f"serving_kv_quant_{arm}", 0.0,
            f"{st.bytes_per_token:.0f} B/token; {resident} resident req @ "
            f"budget; acc {summary['acceptance_rate']:.3f}; {tps:.1f} tok/s",
        ))
    if "none" in out["arms"] and "int8" in out["arms"]:
        a, b = out["arms"]["none"], out["arms"]["int8"]
        out["bytes_per_token_ratio"] = (
            a["bytes_per_token"] / b["bytes_per_token"]
        )
        out["resident_requests_ratio"] = (
            b["max_resident_requests_at_budget"]
            / max(a["max_resident_requests_at_budget"], 1)
        )
        out["acceptance_delta"] = abs(
            b["acceptance_rate"] - a["acceptance_rate"]
        )
        rows.append((
            "serving_kv_quant_ab", 0.0,
            f"{out['bytes_per_token_ratio']:.2f}x fewer bytes/token, "
            f"{out['resident_requests_ratio']:.2f}x resident requests @ "
            f"fixed budget; acceptance delta "
            f"{out['acceptance_delta']:.3f}",
        ))


def run(smoke: bool = False, kv_path: str = "both", paged_attn: str = "auto",
        par_mode: str = "off", kv_quant: str = "none",
        spec_mode: str = "chain", json_path: str = None,
        trace_out: str = None):
    from repro.launch.serve import build_pair
    from repro.serving import Engine, EngineConfig, SamplingParams

    rows = []
    record = {
        "meta": {
            "backend": jax.default_backend(),
            "smoke": smoke,
            "kv_path": kv_path,
            "paged_attn": paged_attn,
            "par_mode": par_mode,
        },
        "configs": [],
    }
    max_tokens = 8 if smoke else 24
    n_req = 4 if smoke else 8
    target, draft = build_pair(seed=0, s_max=256, quantize=False)
    if paged_attn != "auto":
        target = dataclasses.replace(target, paged_attn_impl=paged_attn)
        draft = dataclasses.replace(draft, paged_attn_impl=paged_attn)
    prompts = _prompts(n_req)
    paths = ["paged", "host"] if kv_path == "both" else [kv_path]

    # --- baseline: N sequential single-request drains (a fresh engine per
    # drain — the per-call jit cost every pre-redesign serve_sd call paid)
    sp = SamplingParams(max_tokens=max_tokens)
    t0 = time.perf_counter()
    for p in prompts:
        Engine(target, draft,
               EngineConfig(max_batch=1, page_size=16, draft_len=3,
                            max_model_len=len(p) + max_tokens + 3)).run([p], sp)
    dt_seq = time.perf_counter() - t0
    seq_tps = n_req * max_tokens / dt_seq
    rows.append(("serving_sequential_x%d" % n_req, 0.0, f"{seq_tps:.1f} tok/s"))
    record["sequential"] = {"requests": n_req, "tokens_per_s": seq_tps}

    # --- continuous batching at increasing batch sizes, per kv path
    batch_tps = {}
    round_ms = {}
    # "both" A/Bs the schedulers in their own section; the sweep runs "off"
    sweep_par = par_mode if par_mode in ("off", "wdos") else "off"
    runners = {
        "paged": lambda *a, **k: _run_paged(*a, par_mode=sweep_par, **k),
        "host": _run_host,
    }
    for path in paths:
        for bs in ([2, n_req] if smoke else [2, 4, n_req]):
            outs, summary, dt, eng = runners[path](
                target, draft, prompts, bs, max_tokens
            )
            tps = sum(int(o.shape[0]) for o in outs) / dt
            batch_tps[(path, bs)] = tps
            round_ms[(path, bs)] = dt / max(summary["rounds"], 1) * 1e3
            rows.append((
                f"serving_{path}_b{bs}", 0.0,
                f"{tps:.1f} tok/s; {round_ms[(path, bs)]:.1f} ms/round; "
                f"wdos-model {summary['wdos_modeled_speedup']:.2f}x",
            ))
            cfg_rec = {
                "kv_path": path,
                "par_mode": summary.get("par_mode", "off"),
                "max_batch": bs,
                "requests": n_req,
                "max_tokens": max_tokens,
                "tokens_per_s": tps,
                "ms_per_round": round_ms[(path, bs)],
                "rounds": summary["rounds"],
                "acceptance_rate": summary["acceptance_rate"],
                "wdos_modeled_speedup": summary["wdos_modeled_speedup"],
                "kv_copy_s": summary["kv_copy_s"],
                "table_upload_s": summary.get("table_upload_s", 0.0),
            }
            if "fused" in summary:
                cfg_rec["fused"] = summary["fused"]
            record["configs"].append(cfg_rec)
            if bs == n_req:
                _copy_telemetry(rows, f"serving_{path}_b{bs}", summary)
            if path == "paged" and bs == n_req:
                # steady state: the SAME engine serves another wave with its
                # jitted steps warm — what a long-lived server sees, and
                # what the run-to-drain API could never keep across calls
                outs_w, summary_w, dt_w, _ = _run_paged(
                    target, draft, prompts, bs, max_tokens, warm_engine=eng
                )
                warm_tps = sum(int(o.shape[0]) for o in outs_w) / dt_w
                rows.append((
                    f"serving_paged_warm_b{bs}", 0.0,
                    f"{warm_tps:.1f} tok/s steady-state (reused engine)",
                ))
                record["paged_warm"] = {
                    "max_batch": bs,
                    "tokens_per_s": warm_tps,
                    "ms_per_round": dt_w / max(summary_w["rounds"] -
                                               summary["rounds"], 1) * 1e3,
                }
    for path in paths:
        speedup = batch_tps[(path, n_req)] / seq_tps
        rows.append((
            f"serving_{path}_batch{n_req}_speedup_vs_sequential", 0.0,
            f"{speedup:.2f}x",
        ))
        record[f"{path}_batch_speedup_vs_sequential"] = speedup
    if len(paths) == 2:
        # the residency win isolated from (CPU-smoke-dominating) jit time:
        # host copies O(S_max x B) K/V bytes per round, paged uploads only
        # int32 tables/lengths
        host_cfg = next(c for c in record["configs"]
                        if c["kv_path"] == "host" and c["max_batch"] == n_req)
        paged_cfg = next(c for c in record["configs"]
                         if c["kv_path"] == "paged" and c["max_batch"] == n_req)
        host_ms = host_cfg["kv_copy_s"] / max(host_cfg["rounds"], 1) * 1e3
        paged_ms = (paged_cfg["table_upload_s"]
                    / max(paged_cfg["rounds"], 1) * 1e3)
        ratio = host_ms / max(paged_ms, 1e-9)
        rows.append((
            f"serving_paged_copy_tax_vs_host_b{n_req}", 0.0,
            f"{ratio:.1f}x less per-round host traffic "
            f"({host_ms:.2f} ms K/V copies -> {paged_ms:.2f} ms tables)",
        ))
        record["paged_copy_tax_speedup_vs_host"] = ratio

    # --- page-size sweep: allocator utilization (internal fragmentation)
    record["page_sweep"] = []
    for ps in [4, 32]:
        if paths[0] == "paged":
            _, summary, _, _ = _run_paged(target, draft, prompts, n_req,
                                          max_tokens, page_size=ps)
        else:
            _, summary, _, _ = _run_host(target, draft, prompts, n_req,
                                         max_tokens, page_size=ps)
        st = summary["target_pool"]
        rows.append((
            f"serving_page{ps}_high_water", 0.0,
            f"{st.high_water_pages}/{st.num_pages} pages",
        ))
        record["page_sweep"].append({
            "page_size": ps,
            "high_water_pages": st.high_water_pages,
            "num_pages": st.num_pages,
        })

    # --- compressed-KV A/B (int8 pools + scales vs dense, fixed byte budget)
    if kv_quant != "none":
        record["meta"]["kv_quant"] = kv_quant
        arms = ("none", "int8") if kv_quant == "both" else (kv_quant,)
        _kv_quant_ab(target, draft, prompts, max_tokens, rows, record, arms)

    # --- tree-speculation A/B (top-k branch trees vs single draft chains)
    if spec_mode != "chain":
        record["meta"]["spec_mode"] = spec_mode
        arms = ("chain", "tree") if spec_mode == "both" else (spec_mode,)
        _tree_spec_ab(target, draft, rows, record, arms)

    # --- PAR scheduler A/B (fused cross-request rounds vs two-phase)
    if par_mode == "both":
        _par_ab(target, draft, prompts, max_tokens, rows, record,
                trace_out=trace_out)
    elif trace_out:
        rows.append(("serving_wdos_trace", 0.0,
                     "skipped: --trace-out needs --par-mode both"))

    _bench_paged_attn_rows(rows, record)
    if json_path:
        with open(json_path, "w") as f:
            json.dump(record, f, indent=2, sort_keys=True)
        rows.append(("serving_json", 0.0, json_path))
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="CI-sized run")
    ap.add_argument(
        "--kv-path", choices=["paged", "host", "both"], default="both",
        help="KV residency: device-resident pools, legacy host gather, or A/B",
    )
    ap.add_argument(
        "--paged-attn", choices=["auto", "gather", "pallas"], default="auto",
        help="paged attention impl: backend auto-select (pallas on TPU/GPU, "
             "gather on CPU), exact device gather, or the Pallas kernel",
    )
    ap.add_argument(
        "--par-mode", choices=["off", "wdos", "both"], default="off",
        help="round scheduler: two-phase draft-then-verify, fused "
             "cross-request PAR (WDOS mixed phase plans), or 'both' to "
             "also A/B them on a staggered-admission workload",
    )
    ap.add_argument(
        "--kv-quant", choices=["none", "int8", "both"], default="none",
        help="KV storage precision for the compressed-KV section: dense "
             "(skip the section), int8-only, or 'both' to A/B int8 vs "
             "dense at a fixed pool byte budget (bytes/token + resident "
             "request capacity + acceptance delta)",
    )
    ap.add_argument(
        "--spec-mode", choices=["chain", "tree", "both"], default="chain",
        help="speculation shape for the tree-spec section: chain (skip the "
             "section), tree-only, or 'both' to A/B top-k branch trees vs "
             "single draft chains (accepted tokens per request-round on a "
             "low-acceptance sampled workload + greedy bit-identity)",
    )
    ap.add_argument(
        "--json", default="BENCH_serving.json", metavar="PATH",
        help="machine-readable output (perf trajectory across PRs); "
             "'' disables",
    )
    ap.add_argument(
        "--trace-out", default="", metavar="PATH",
        help="with --par-mode both: record the wdos arm with the span "
             "tracer and export the staggered round timeline as "
             "Chrome-trace JSON (open in https://ui.perfetto.dev)",
    )
    args = ap.parse_args(argv)
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    print("name,us_per_call,derived")
    for n, us, derived in run(
        smoke=args.smoke, kv_path=args.kv_path, paged_attn=args.paged_attn,
        par_mode=args.par_mode, kv_quant=args.kv_quant,
        spec_mode=args.spec_mode,
        json_path=args.json or None, trace_out=args.trace_out or None,
    ):
        print(f"{n},{us:.1f},{derived}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
