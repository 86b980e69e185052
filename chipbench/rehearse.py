"""Compile a cell's programs for a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 -m chipbench.rehearse --workload <cell>

Lowers and compiles, at the cell's real sizes and for one chip of a
described ``v5e:2x2`` topology, the engine's verify step (target, window
draft_len + 1), its draft step (window 1) and the prefill forward at the
longest prompt of the traffic grid, then prints each program's
``memory_analysis`` with the bytes of weights and pools the cell keeps
resident.  Nothing runs: this says what the chip's compiler accepts and
what fits, never how fast it runs.
"""
from __future__ import annotations

import argparse
import json
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _nbytes(tree) -> int:
    import jax

    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.models import lm
    from repro.serving import paged_cache as pc
    from repro.serving.engine import ServingModel, _make_paged_step

    from chipbench import harness
    from chipbench.spec import Bench

    bench = Bench()
    w = bench.workload(args.workload)
    config, traffic = bench.config(w["config"]), bench.traffic(w["traffic"])
    served = bench.served(config["served"])
    sv = config["serving"]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def spec(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree)

    key = jax.random.PRNGKey(0)
    tparams, dparams = jax.eval_shape(lambda k: served.draw(k, config), key)
    s_max = harness.max_model_len(config, traffic)
    page = config["engine"]["page_size"]
    batch = traffic["engine"]["max_batch"]
    n_pages = -(-traffic["engine"]["pool_tokens"] // page)
    window = config["engine"]["draft_len"] + 1
    mp = pc.pages_for(s_max, page)
    report = {"workload": args.workload, "s_max": s_max, "max_batch": batch,
              "pool_pages": n_pages, "target_param_bytes": _nbytes(tparams),
              "draft_param_bytes": _nbytes(dparams)}
    # the paged kernel picks interpret mode from the default backend, which
    # here is the CPU: lower it as the chip would
    jax.default_backend = lambda: "tpu"
    for role, params, cfg, mode, w_len in (
        ("verify", tparams, served.program_config(config, "target"), sv["target"], window),
        ("draft", dparams, served.program_config(config, "draft"), sv["draft"], 1),
    ):
        model = ServingModel(cfg=cfg, params=None, mode=mode, s_max=s_max,
                             paged_attn_impl="pallas")
        kvh, hd = cfg.n_kv, cfg.hd
        store = {n: jax.ShapeDtypeStruct((cfg.n_layers, n_pages + 1, kvh, page, hd),
                                         jnp.dtype(sv["dtype"]), sharding=chip)
                 for n in ("k", "v")}
        report[f"{role}_pool_bytes"] = _nbytes(store)
        step = _make_paged_step(model)
        compiled = step.lower(
            spec(params), jax.ShapeDtypeStruct((batch, w_len), jnp.int32, sharding=chip),
            store, jax.ShapeDtypeStruct((batch, mp), jnp.int32, sharding=chip),
            jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=chip),
        ).compile()
        report[f"{role}_memory"] = str(compiled.memory_analysis())
        report[f"{role}_calls_kernel"] = "tpu_custom_call" in compiled.as_text()
        cache = jax.eval_shape(lambda: lm.init_cache(cfg, 1, s_max, tp=1))
        plen = max(traffic["prompt_len"]["grid"]) - 1
        prefill = jax.jit(lambda p, t, c, model=model: model._apply(p, t, c))
        compiled = prefill.lower(
            spec(params), jax.ShapeDtypeStruct((1, plen), jnp.int32, sharding=chip),
            spec(cache)).compile()
        report[f"{role}_prefill_memory"] = str(compiled.memory_analysis())
    print(json.dumps(report), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
