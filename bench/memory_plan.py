#!/usr/bin/env python3
"""Memory plan of a configuration's serving engine on a described chip.

    JAX_PLATFORMS=cpu python3 bench/memory_plan.py <config> <traffic> [n_blocks]

Compiles the engine's prefill and decode step Programs at the
configuration's sizes for a TPU v5e that is described, not attached (no
chip is needed), and prints what ``compiled.memory_analysis()`` reports:
the weights, the page pool (donated, so aliased to the new pool), the
logits and the temporaries.  ``n_blocks`` defaults to the configuration
file's.  The result is what the configuration file's ``memory_plan``
records; the pool is sized so that the weights, the pool and the larger
step's temporaries and outputs fit the chip with room to spare.
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import run
    from repro.core.program import compile as compile_graph
    from repro.models.graph_lm import (GraphLMConfig, build_paged_decode_graph,
                                       build_paged_prefill_graph)
    jax.config.update("jax_enable_compilation_cache", False)
    cfg_name, mix_name = argv[:2]
    layout = run.Layout.__new__(run.Layout)
    layout.roots = [BENCH]
    cfg = layout.json("configs", cfg_name)
    mix = layout.json("traffic", mix_name)
    fam = layout.module("models", cfg["family"])
    sv = cfg["serving"]
    n_blocks = int(argv[2]) if len(argv) > 2 else int(sv["n_blocks"])
    page = int(sv["page_size"])
    cap = -(-run.traffic_mod.max_context(mix) // page) * page
    gcfg = GraphLMConfig(**fam.serving_config(cfg))
    # zero pages are never touched: only shapes are read from them
    params = {k: np.zeros(s, np.float32) for k, s in fam._shapes(cfg).items()}
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    kw = dict(batch=int(sv["n_slots"]), n_blocks=n_blocks, page_size=page,
              max_pages=cap // page)
    report = {"config": cfg_name, "traffic": mix_name, "cache_cap": cap,
              "n_blocks": n_blocks,
              "weights_bytes": int(sum(p.nbytes for p in params.values())),
              "pool_bytes": n_blocks * page * fam.kv_bytes_per_token(cfg)}
    for name, graph in (
            ("prefill", build_paged_prefill_graph(gcfg, params,
                                                  chunk=int(sv["chunk"]), **kw)),
            ("decode", build_paged_decode_graph(gcfg, params, **kw))):
        prog = compile_graph(graph)
        pspec = {k: jax.ShapeDtypeStruct(np.shape(v), jnp.float32,
                                         sharding=one)
                 for k, v in graph.params.items()}
        ispec = {k: jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype),
                                         sharding=one)
                 for k, s in graph.inputs.items()}
        cache = [k for k in ispec if k.startswith("cache_")]
        fn = jax.jit(lambda p, i, c: prog._trace(p, {**i, **c}),
                     donate_argnums=(2,))
        ma = fn.lower(pspec, {k: v for k, v in ispec.items()
                              if k not in cache},
                      {k: ispec[k] for k in cache}).compile().memory_analysis()
        report[name] = {k: int(getattr(ma, k)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes")}
        report[name]["total_bytes"] = (
            ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    print(json.dumps(report, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
