#!/usr/bin/env bash
# CI entry point: tier-1 tests + smoke serving benchmarks.
# Mirrors .github/workflows/ci.yml so the same command runs locally.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

echo "== docs check (internal links + paper-concept table module refs) =="
python scripts/check_docs.py

echo "== serving benchmark (smoke, Engine over device-resident paged KV) =="
# Emits machine-readable BENCH_serving.json (tokens/s, rounds, acceptance
# rate, copy telemetry) so the perf trajectory is tracked across PRs.
# --par-mode both also A/Bs the fused cross-request PAR scheduler against
# two-phase rounds on a staggered workload (the PAR smoke: rounds-to-drain
# + fused-slot occupancy land in the JSON).  --trace-out records the wdos
# arm with the span tracer and exports the staggered round timeline as
# Perfetto-loadable Chrome-trace JSON (validated below).  --kv-quant both
# A/Bs int8 KV pools against dense at a fixed pool byte budget (bytes/token,
# resident-request capacity, acceptance delta — gated below).  --spec-mode
# both A/Bs tree-structured speculation against single-chain drafting on a
# low-acceptance sampled workload (accepted tokens per request-round +
# greedy bit-identity — gated below).
python -m benchmarks.bench_serving --smoke --kv-path paged --par-mode both \
    --kv-quant both --spec-mode both \
    --json BENCH_serving.json --trace-out TRACE_wdos.json

echo "== paged-path kernel smoke (batch 4, Pallas interpret mode) =="
# Exercises the kernel-wired decode path end to end every run: the Engine
# dispatching decode+verify attention through kernels/paged_attn.py.
python -m benchmarks.bench_serving --smoke --kv-path paged --paged-attn pallas \
    --json BENCH_serving_pallas.json

echo "== HTTP serving front-end smoke (stream, stop/top_p, disconnect->abort, 429, /metrics) =="
# Spins up serving/server.py over asyncio streams and drives it through the
# shared serving/http_client.py: SSE bit-identity vs Engine.run, a
# mid-stream disconnect that must return every pool page, a fail-fast 429
# under saturation, and a GET /metrics scrape asserting the core Prometheus
# series; headline observability gauges merge into BENCH_serving.json.
python scripts/server_smoke.py --json BENCH_serving.json

echo "== open-loop Poisson load harness (TTFT/ITL/E2E percentiles) =="
# Appends "async_load" latency percentiles (A/B par_mode off vs wdos at
# several arrival rates) into the BENCH_serving.json written above.
python -m benchmarks.bench_server --smoke --json BENCH_serving.json

echo "== shared-prefix workload A/B (prefix_cache on vs off) =="
# Multi-tenant Poisson workload (N system prompts x M users) against two
# engines fed the SAME arrival schedule; appends the "prefix_cache" record
# (hit rate, prefill tokens saved, TTFT A/B, bit-identity) — gated below.
python -m benchmarks.bench_server --smoke --shared-prefix \
    --json BENCH_serving.json

echo "== serving perf record =="
python - <<'EOF'
import json
for p in ("BENCH_serving.json", "BENCH_serving_pallas.json"):
    r = json.load(open(p))
    cfgs = {(c["kv_path"], c["max_batch"]): c["tokens_per_s"] for c in r["configs"]}
    print(p, {k: round(v, 1) for k, v in cfgs.items()})
par = json.load(open("BENCH_serving.json")).get("par")
if par:
    print("PAR A/B rounds-to-drain:",
          {m: par[m]["rounds_to_drain"] for m in par},
          "fused occupancy:",
          round(par["wdos"].get("fused", {}).get("occupancy", 0.0), 3))
load = json.load(open("BENCH_serving.json")).get("async_load")
if load:
    for mode in load["meta"]["modes"]:
        for rate, e in sorted(load[mode].items(), key=lambda kv: float(kv[0])):
            print(f"async {mode} @{rate} req/s:",
                  f"{e['tokens_per_s']:.1f} tok/s,",
                  f"TTFT p99 {e['ttft_s']['p99']*1e3:.0f} ms,",
                  f"E2E p99 {e['e2e_s']['p99']*1e3:.0f} ms")
obs = json.load(open("BENCH_serving.json")).get("observability")
if obs:
    print("observability:", {k: round(v, 4) if isinstance(v, float) else v
                             for k, v in sorted(obs.items())})
EOF

echo "== compressed-KV gate (int8 capacity win + acceptance bound) =="
# int8 KV must (a) store >= 1.8x fewer bytes per token, (b) fit >= 1.8x
# more resident requests at the same pool byte budget, and (c) keep the
# speculative acceptance rate within 0.05 absolute of dense storage — the
# contract that makes kv_quant="int8" a safe opt-in.
python - <<'EOF'
import json
kvq = json.load(open("BENCH_serving.json"))["kv_quant"]
bytes_ratio = kvq["bytes_per_token_ratio"]
resident_ratio = kvq["resident_requests_ratio"]
delta = kvq["acceptance_delta"]
assert bytes_ratio >= 1.8, f"bytes/token ratio {bytes_ratio:.2f}x < 1.8x"
assert resident_ratio >= 1.8, \
    f"resident-request ratio {resident_ratio:.2f}x < 1.8x"
assert delta <= 0.05, f"int8 acceptance delta {delta:.3f} > 0.05"
print(f"kv_quant OK: {bytes_ratio:.2f}x fewer bytes/token, "
      f"{resident_ratio:.2f}x resident requests @ fixed budget, "
      f"acceptance delta {delta:.3f} <= 0.05")
EOF

echo "== prefix-cache gate (sharing must hit, save prefill, stay bit-identical) =="
# The shared-prefix A/B is only a win if (a) the radix tree actually hits,
# (b) sharing skips a majority of prefill rows, and (c) the emitted tokens
# are bit-identical to sharing off — the determinism contract that makes
# prefix_cache=True a safe default for multi-tenant serving.
python - <<'EOF'
import json
pc = json.load(open("BENCH_serving.json"))["prefix_cache"]
hit = pc["hit_rate"]
saved = pc["prefill_tokens_saved_frac"]
assert pc["bit_identical"], "prefix sharing changed emitted tokens"
assert hit > 0.0, f"prefix hit rate {hit:.2f} — cache never hit"
assert saved > 0.5, f"prefill tokens saved {saved:.2%} <= 50%"
print(f"prefix_cache OK: hit_rate {hit:.2f}, "
      f"{saved:.0%} prefill rows skipped, "
      f"TTFT p50 {pc['ttft_p50_off_s']*1e3:.0f} -> "
      f"{pc['ttft_p50_on_s']*1e3:.0f} ms, bit-identical")
EOF

echo "== tree-speculation gate (branch trees must out-accept chains, losslessly) =="
# Tree speculation pays for its extra verified nodes only if it commits more
# tokens per round than chain drafting on the SAME workload — and it is only
# shippable if greedy output is untouched (branching changes rounds, never
# content).  Gate both, on the A/B the bench just recorded.
python - <<'EOF'
import json
ts = json.load(open("BENCH_serving.json"))["tree_spec"]
chain = ts["arms"]["chain"]["accepted_per_request_round"]
tree = ts["arms"]["tree"]["accepted_per_request_round"]
assert ts["greedy_bit_identical"], "greedy tree stream != greedy chain stream"
assert tree > chain, \
    f"tree accepted/round {tree:.3f} <= chain {chain:.3f}"
print(f"tree_spec OK: {chain:.3f} -> {tree:.3f} accepted tok/request-round "
      f"({ts['accepted_per_round_ratio']:.2f}x), greedy bit-identical")
EOF

echo "== wdos round-timeline trace (Chrome-trace schema gate) =="
# The bench's --trace-out must round-trip through the Chrome-trace schema
# checker non-empty — the same JSON a developer drops into Perfetto — with
# the engine track carrying the wdos fused-slot spans.
python - <<'EOF'
import json
from repro.serving import validate_chrome_trace
trace = json.load(open("TRACE_wdos.json"))
problems = validate_chrome_trace(trace)
assert not problems, problems[:5]
events = trace["traceEvents"]
assert len(events) > 10, f"trace suspiciously small: {len(events)} events"
meta = {e["tid"]: e["args"]["name"] for e in events if e["ph"] == "M"}
tracks = set(meta.values())
assert "engine" in tracks and any(t.startswith("row") for t in tracks), tracks
eng_tids = {tid for tid, name in meta.items() if name == "engine"}
eng_spans = {e["name"] for e in events
             if e["ph"] == "X" and e["tid"] in eng_tids}
assert "fused_slot" in eng_spans, f"engine track spans: {sorted(eng_spans)}"
print(f"TRACE_wdos.json OK: {len(events)} events across "
      f"{len(tracks)} tracks {sorted(tracks)}")
EOF

echo "== perf-regression sentinel (BENCH_history.jsonl trajectory gate) =="
# First PROVE the gate works on synthetic trajectories (an injected -70%
# collapse must exit 1; ±10% noise and first-run bootstrap must pass),
# then gate the real record vs the median of recent runs and append it.
python scripts/perf_sentinel.py --self-test
python scripts/perf_sentinel.py --bench BENCH_serving.json \
    --history BENCH_history.jsonl

echo "== property-based suites (hypothesis-randomized oracles) =="
# hypothesis is a first-class dev dependency (requirements-dev.txt): with
# it installed the dedicated property module runs here as a gate, and the
# @given oracles embedded in test_kernels/test_quantization/test_rotation/
# test_paged_attn run inside the tier-1 suite below.  A bare runtime env
# (requirements.txt only) degrades to per-test skips via tests/_optional.py
# instead of failing collection — so this stanza notices, never breaks.
if python -c "import hypothesis" >/dev/null 2>&1; then
    python -m pytest -x -q tests/test_properties.py
else
    echo "hypothesis not installed: property tests skip individually in the tier-1 run"
fi

echo "== tier-1 tests (gate) =="
# Mesh-dependent tests in test_launch.py / test_models.py build their
# meshes through launch/mesh.py (Auto axes, jax.set_mesh); only the
# genuinely multi-device test_substrate.py case stays skipif-guarded on
# single-device CPU, so the whole suite gates.  tests/test_tpu_compile.py
# compiles the serving kernels for a described v5e; it skips where no TPU
# compiler is installed.
python -m pytest -x -q
