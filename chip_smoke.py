#!/usr/bin/env python3
"""Smoke run of the serving engine on a TPU at phi3-mini widths.

Drives the engine's main path through the entry points a user calls
(``build_lm_serving`` -> ``Engine.submit`` / ``Engine.run``) on seeded
random weights, in one process, and checks every phase against the
engine's ``UnbatchedReference``::

    python chip_smoke.py              # one chip: phases default, pallas, kv8
    python chip_smoke.py --chips 4    # four chips: TP=4 engine vs TP=1 engine

The model has phi3-mini-3.8b's widths (``repro.configs``) cut to 4 of its
32 layers, so that the engine's fp32 weight copies (one per compiled
Program, four live at a time) fit one 16 GB v5e.  The engine has no RoPE,
so this is phi3-mini's shape, not phi3-mini.

It exits non-zero and prints no result line when JAX finds no TPU, and
on any failed check.  The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
This is a smoke run, not a benchmark: its times are one-off readings.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

N_LAYERS = 4          # of phi3-mini's 32; see the module docstring
SEED = 0              # weights and prompts
N_REQUESTS = 6
PROMPT_LENS = np.linspace(32, 448, N_REQUESTS).astype(int)
MAX_NEW = 32
SERVE_KW = dict(n_slots=4, chunk=64, cache_cap=512, paged=True)
PAGE = 16
# int8 tiles are (32, 128): a 32-row page fills whole sublane tiles
KV8_PAGE = 32

# Tolerances on the logits that chose each request's first token, as
# max |engine - reference| over the vocabulary.  With these random weights
# the logits are about N(0, 1): the final RMSNorm gives unit-RMS rows and
# head_w has variance 1/d_model.  On a TPU an fp32 matmul at default
# precision rounds its operands to bfloat16, a relative step of 2**-8.
# The engine (4 slots, paged kernels) and the reference (1 slot, dense
# kernels) add in different orders, so a last-bit fp32 difference can flip
# one such rounding in a later layer: a few thousandths per layer, a few
# hundredths at most over four layers and the head.  A wrong mask, page or
# offset moves logits by O(1).
FP32_TOL = 5e-2
# int8 pages add each K/V element's rounding, up to half a step of
# absmax/127 of its (page, kv head) tile, i.e. up to 0.4% of the largest
# element, to every attention score and output; the reference keeps fp32
# caches.  On the CPU at these widths with one layer that moved the
# first-token logits by 0.026; over four layers and with the bfloat16
# rounding above it stays under KV8_TOL, which is still well under the
# O(1) moves of a wrong page or scale.
KV8_TOL = 2.5e-1


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, read from its
    own monitoring events.  A persistent-cache hit skips the backend
    compile, so then only tracing and lowering count."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration


class FirstLogits:
    """Wraps a stepper's ``prefill`` and keeps, per request, the logits row
    at its last prompt position: the row the engine's argmax turns into
    the request's first token.  A prefill row is matched to a request by
    where it ends and by its tokens, so no engine internals are read."""

    def __init__(self, stepper, prompts):
        self.rows = {}
        self._prompts = prompts
        self._prefill = stepper.prefill
        stepper.prefill = self

    def __call__(self, tokens, start, n_new):
        logits = self._prefill(tokens, start, n_new)
        for s in np.flatnonzero(n_new):
            lo, n = int(start[s]), int(n_new[s])
            for uid, p in self._prompts.items():
                if len(p) == lo + n and np.array_equal(p[lo:], tokens[s, :n]):
                    self.rows[uid] = logits[s, n - 1].copy()
        return logits


def require(cond, msg):
    if not cond:
        raise SystemExit(f"FAILED: {msg}")


def phi3_width_config():
    """GraphLMConfig with phi3-mini-3.8b's widths, depth cut to N_LAYERS."""
    from repro.configs import get_config
    from repro.models.graph_lm import GraphLMConfig
    src = get_config("phi3-mini-3.8b")
    cfg = GraphLMConfig(vocab=src.vocab, d_model=src.d_model,
                        n_layers=N_LAYERS, n_heads=src.n_heads,
                        n_kv_heads=src.n_kv_heads, d_ff=src.d_ff)
    require(cfg.d_head == src.head_dim,
            f"d_model // n_heads = {cfg.d_head} != head_dim {src.head_dim}")
    print(f"config: {src.name} widths d_model={cfg.d_model} "
          f"heads={cfg.n_heads}q/{cfg.n_kv_heads}kv head_dim={cfg.d_head} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab}")
    print(f"reduced: n_layers {src.n_layers}→{cfg.n_layers}: fp32 "
          f"weights, one device copy per Program")
    return cfg


def make_prompts(vocab, seed):
    rng = np.random.default_rng(seed)
    return {uid: rng.integers(0, vocab, size=int(n)).astype(np.int32)
            for uid, n in enumerate(PROMPT_LENS)}


def kernel_checks(engine):
    """Phase (b)'s proof that the Pallas kernels run compiled: every
    attention, rmsnorm and dense node is on ``pallas`` and the lowered
    step programs hold Mosaic custom calls."""
    summary = engine.stepper.backend_summary()
    for phase, per_op in summary.items():
        for op, counts in per_op.items():
            if "attention" in op or op in ("rmsnorm", "dense"):
                require(set(counts) == {"pallas"},
                        f"{phase}: {op} on {sorted(counts)}, not pallas")
        require(any("attention" in op for op in per_op),
                f"{phase}: no attention node")
    for name in ("prefill_program", "decode_program"):
        hlo = getattr(engine.stepper, name).lower().as_text()
        n = hlo.count("tpu_custom_call")
        require(n > 0, f"{name}: lowered HLO has no tpu_custom_call")
        print(f"  {name}: {n} tpu_custom_call in the lowered HLO")


def memory_stat(devices, key):
    """``memory_stats()[key]`` per device (one value on one chip)."""
    vals = [(d.memory_stats() or {}).get(key) for d in devices]
    return vals[0] if len(vals) == 1 else vals


def run_phase(name, cfg, params, prompts, clock, devices, *, tol,
              check_kernels=False, **serve_kw):
    """Build one engine, serve every request, compare with the reference.
    Returns the engine's tokens per request; the engine is freed here."""
    from repro.runtime.engine import EngineRequest, build_lm_serving
    in_use = memory_stat(devices, "bytes_in_use")
    compile_s0 = clock.seconds
    t0 = time.perf_counter()
    engine, ref = build_lm_serving(cfg, params=params, **{**SERVE_KW,
                                                         **serve_kw})
    first = FirstLogits(engine.stepper, prompts)
    reqs = [EngineRequest(uid=uid, prompt=p, max_new_tokens=MAX_NEW)
            for uid, p in prompts.items()]
    for r in reqs:
        require(engine.submit(r), f"{name}: request {r.uid} rejected "
                                  f"({r.dropped})")
    engine.run()
    wall_s = time.perf_counter() - t0
    compile_s = clock.seconds - compile_s0
    done = [r for r in reqs if r.done and r.dropped is None]
    print(f"phase {name}: finished {len(done)}/{len(reqs)}, compile "
          f"{compile_s:.1f} s, wall {wall_s:.1f} s (compile included), "
          f"bytes_in_use at start {in_use}, peak_bytes_in_use "
          f"{memory_stat(devices, 'peak_bytes_in_use')}")
    require(len(done) == len(reqs), f"{name}: not every request finished")
    if check_kernels:
        kernel_checks(engine)

    worst, agree, prefix, total = 0.0, 0, 0, 0
    for r in reqs:
        ref_logits = []
        ref_tokens = ref.generate(r.prompt, MAX_NEW, chunk=SERVE_KW["chunk"],
                                  logits_out=ref_logits)
        diff = float(np.max(np.abs(first.rows[r.uid] - ref_logits[0])))
        worst = max(worst, diff)
        top2 = np.sort(ref_logits[0])[-2:]
        require(r.out_tokens[0] == ref_tokens[0]
                or top2[1] - top2[0] <= 2 * tol,
                f"{name}: request {r.uid} first token {r.out_tokens[0]} != "
                f"reference {ref_tokens[0]} with a top-2 gap over 2*tol")
        same = np.asarray(r.out_tokens) == np.asarray(ref_tokens)
        agree += int(same.sum())
        prefix += int(np.argmin(same)) if not same.all() else len(same)
        total += len(same)
    print(f"  vs reference: first-token logits max|diff| {worst:.3g} "
          f"(tol {tol:g}); generated tokens agreeing {agree}/{total}, "
          f"in matching prefixes {prefix}/{total}; peak_bytes_in_use "
          f"with the reference {memory_stat(devices, 'peak_bytes_in_use')}")
    require(worst <= tol, f"{name}: first-token logits differ by {worst:.3g}"
                          f" > {tol:g}")
    tokens = {r.uid: list(r.out_tokens) for r in reqs}
    del engine, ref, first, reqs, done
    gc.collect()
    return tokens


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases default, pallas and kv8 on one chip; "
                         "4: only the TP=4 engine against the TP=1 engine")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"jax {jax.__version__}: platform={dev.platform} "
          f"device_kind={dev.device_kind} device_count={len(devices)}")
    if dev.platform != "tpu":
        print("no TPU found: this smoke run needs a TPU", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"no repro package under {src}", file=sys.stderr)
        return 1
    sys.path.insert(0, src)

    from repro.launch.compile_cache import enable_compile_cache
    from repro.core.selector import FixedPolicy
    from repro.models.graph_lm import init_lm_params
    print(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    cfg = phi3_width_config()
    t0 = time.perf_counter()
    params = init_lm_params(cfg, seed=SEED)
    print(f"weights: {sum(v.size for v in params.values()):,} fp32 params "
          f"from seed {SEED} in {time.perf_counter() - t0:.1f} s")
    prompts = make_prompts(cfg.vocab, SEED)
    print(f"traffic: {N_REQUESTS} requests, prompts {PROMPT_LENS.tolist()} "
          f"tokens, max_new_tokens={MAX_NEW}, {SERVE_KW}, page_size={PAGE}")

    if args.chips == 1:
        pallas = FixedPolicy(prefer=("pallas", "xla", "ref"))
        run_phase("default", cfg, params, prompts, clock, devices[:1],
                  tol=FP32_TOL, page_size=PAGE)
        run_phase("pallas", cfg, params, prompts, clock, devices[:1],
                  tol=FP32_TOL, check_kernels=True, policy=pallas,
                  page_size=PAGE)
        run_phase("kv8", cfg, params, prompts, clock, devices[:1],
                  tol=KV8_TOL, check_kernels=True, policy=pallas,
                  page_size=KV8_PAGE, kv_dtype="int8")
    else:
        tp1 = run_phase("tp1", cfg, params, prompts, clock, devices[:4],
                        tol=FP32_TOL, page_size=PAGE)
        tp4 = run_phase("tp4", cfg, params, prompts, clock, devices[:4],
                        tol=FP32_TOL, page_size=PAGE, tp=4)
        same = sum(int(np.sum(np.asarray(tp4[u]) == np.asarray(tp1[u])))
                   for u in tp1)
        print(f"tp4 vs tp1: generated tokens agreeing "
              f"{same}/{N_REQUESTS * MAX_NEW}")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
