"""Serving driver: continuous batching over a reduced-config model, or the
Program-backed engine over the graph LM.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b \
        --requests 16 --slots 4

    PYTHONPATH=src python -m repro.launch.serve --engine [--int8] \
        [--paged] [--kv-dtype int8] --requests 16 --slots 4 --chunk 8

Default mode submits a stream of random-prompt requests and runs the
slot-based continuous batcher (prefill-on-admit, batched decode) over an
:class:`repro.models.lm.LM`; on a real pod the same batcher drives the
sharded decode step from runtime/serve.py.  ``--engine`` instead serves
compiled Programs (``repro.runtime.engine``): chunked prefill, deadlines,
per-token streaming, EngineMetrics — and with ``--int8`` the decode and
prefill steps are post-training-quantized Programs.  ``--paged`` swaps in
the paged KV cache; ``--kv-dtype int8`` stores its pages as int8 with
per-(page, kv-head) scales (implies ``--paged``).  ``--tp N`` (or
``--mesh model=N``) serves tensor-parallel over the first N devices —
token-identical output, attention sharded over heads (see
``docs/serving-guide.md`` §10).
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.configs import get_config, get_reduced
from repro.models.lm import LM
from repro.runtime.batching import ContinuousBatcher, Request


def run_engine(args) -> None:
    from repro.models.graph_lm import GraphLMConfig
    from repro.runtime.engine import EngineRequest, build_lm_serving

    cfg = GraphLMConfig()
    cache_cap = max(args.cache_cap, args.chunk + args.max_new + 16)
    paged = args.paged or args.kv_dtype != "float32"
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_serving_mesh
        # "model=N" (the serving shape); extra axes would need a custom
        # Mesh — keep the flag honest about what the engine consumes
        axis, _, size = args.mesh.partition("=")
        if axis != "model":
            raise SystemExit(f"--mesh wants model=N, got {args.mesh!r}")
        mesh = make_serving_mesh(int(size))
    engine, _ = build_lm_serving(
        cfg, n_slots=args.slots, chunk=args.chunk, cache_cap=cache_cap,
        quantize="int8" if args.int8 else None,
        paged=paged, kv_dtype=args.kv_dtype,
        mesh=mesh, tp=args.tp)
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(args.requests):
        prompt = rng.integers(0, cfg.vocab,
                              size=int(rng.integers(2, 14))).astype(np.int32)
        reqs.append(EngineRequest(uid=i, prompt=prompt,
                                  max_new_tokens=args.max_new))
    for r in reqs:
        engine.submit(r)
    engine.run(max_ticks=100_000)
    m = engine.metrics.summary()
    tp_note = ""
    if mesh is not None or args.tp:
        part = engine.stepper.decode_program.partition
        tp_note = (f" mesh={dict(part['mesh'])}" if part is not None
                   else " mesh=?")
    print(f"engine: slots={args.slots} chunk={args.chunk} "
          f"int8={args.int8} paged={paged} kv_dtype={args.kv_dtype} "
          f"requests={len(reqs)}{tp_note}")
    print(json.dumps(m, indent=1, sort_keys=True))
    if paged:
        s = engine.stepper.pool.stats()
        print(f"paged pool: {s['n_blocks']} blocks x {s['page_size']} rows "
              f"({s['kv_dtype']}, {s['page_bytes']}B/page), "
              f"hit rate {s['hit_rate']:.0%}, CoW {s['cow_count']}")
    for r in reqs[:3]:
        print(f"  req{r.uid}: prompt[:4]={r.prompt[:4].tolist()} "
              f"-> out[:6]={r.out_tokens[:6]}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--full", action="store_true",
                    help="full config (needs a real pod)")
    ap.add_argument("--engine", action="store_true",
                    help="serve compiled Programs via the serving engine")
    ap.add_argument("--int8", action="store_true",
                    help="with --engine: serve int8-quantized Programs")
    ap.add_argument("--paged", action="store_true",
                    help="with --engine: serve through the paged KV cache")
    ap.add_argument("--kv-dtype", choices=("float32", "int8"),
                    default="float32",
                    help="with --engine: paged KV page storage dtype "
                         "(int8 implies --paged)")
    ap.add_argument("--chunk", type=int, default=8,
                    help="with --engine: prefill chunk size")
    ap.add_argument("--tp", type=int, default=None,
                    help="with --engine: tensor-parallel degree (1-D "
                         '("model",) serving mesh over the first N '
                         "devices; fake devices via XLA_FLAGS="
                         "--xla_force_host_platform_device_count)")
    ap.add_argument("--mesh", default=None, metavar="model=N",
                    help="with --engine: explicit serving mesh spec "
                         "(alternative to --tp)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-cap", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=12)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.engine:
        run_engine(args)
        return

    cfg = get_config(args.arch) if args.full else get_reduced(args.arch)
    if cfg.n_encoder_layers or cfg.frontend == "embeds":
        raise SystemExit("serve driver demos token-LM archs")
    model = LM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    batcher = ContinuousBatcher(model, params, n_slots=args.slots,
                                cache_cap=args.cache_cap, eos_id=1)
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i,
                    prompt=rng.integers(2, cfg.vocab,
                                        size=int(rng.integers(4, 12))
                                        ).astype(np.int32),
                    max_new_tokens=args.max_new)
            for i in range(args.requests)]
    for r in reqs:
        batcher.submit(r)

    t0 = time.time()
    batcher.run(max_steps=5000)
    dt = time.time() - t0
    n_out = sum(len(r.out_tokens) for r in reqs)
    print(f"arch={cfg.name} requests={len(reqs)} slots={args.slots}")
    print(f"generated {n_out} tokens in {dt:.2f}s "
          f"({n_out/dt:,.1f} tok/s), decode steps={batcher.steps}, "
          f"slot utilisation={batcher.utilisation:.0%}")
    done = sum(r.done for r in reqs)
    print(f"completed {done}/{len(reqs)}")
    for r in reqs[:3]:
        print(f"  req{r.uid}: prompt[:4]={r.prompt[:4].tolist()} "
              f"-> out[:6]={r.out_tokens[:6]}")


if __name__ == "__main__":
    main()
