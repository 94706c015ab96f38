"""Serving benchmark: the Program-backed engine under sustained traffic.

Measures, on the example graph LM:

* batched engine throughput vs. the unbatched reference loop (the
  continuous-batching win — tokens/s at n_slots should be well above the
  one-request-at-a-time loop);
* chunked-prefill latency isolation: the max inter-token gap of an
  in-flight decode while a long prompt is admitted, for chunked vs.
  one-shot prefill, against the wall time of one full-prompt prefill;
* per-step dispatch overhead of ``Program.__call__`` (kwargs + validation)
  vs. the ``Program.bind`` fast path;
* token-exactness of the engine against the unbatched reference;
* per-op backend assignments of the serving Programs, plus a backend
  sweep: prefill/decode step throughput with the serving ops pinned to
  each registered backend, normalised against ``ref``;
* an autotune pass: the serving Programs compiled under ``AutotunePolicy``
  with measurements persisted to the on-disk autotune cache;
* trace-driven load (``"load"`` JSON section): a seeded bursty trace with
  priority tiers and shared prefix populations (``repro.runtime.loadgen``)
  against a paged self-healing engine with bounded admission — goodput
  under SLO (p99 TTFT + p99 inter-token gap in deterministic ticks),
  overload shedding and per-tier breakdowns;
* tier-aware overload scheduling (``"overload"`` JSON section): the SAME
  seeded 2x-offered-load trace against the same engine shape under two
  policies — tier-blind FIFO (priorities stripped at submit) vs
  tier-aware (low-tier queue shedding + TTFT-budget preemption) — scored
  on high-tier SLO attainment.  ``validate_record`` enforces the strict
  win: tier-aware high-tier attainment must exceed the tier-blind
  baseline's, or the record is invalid;
* the paged KV cache (``"paged"`` JSON section): max concurrent requests
  at equal memory, dense vs paged; prefix-hit vs cold TTFT (wall time AND
  deterministic prefill-tick counts) on a shared-prefix workload;
  token-exactness of the paged engine vs the dense reference; block-pool
  stats (hit rate, CoW count, fragmentation);
* speculative decoding (``"spec"`` JSON section): a decode-heavy workload
  on the engine with greedy draft/verify speculation (one unrolled draft
  Program call plus one batched-verify call per tick) against the same
  engine with speculation off — draft acceptance rate, decode tokens/s
  speculative vs baseline, and the token-exactness flag vs the unbatched
  reference.

Emits a JSON record (p50/p95 latency, TTFT, busy-slot fraction, tokens/s,
gaps, dispatch) to stdout or ``--json``; ``--smoke`` is the fast CI
configuration (tiny model, n_slots=2).

Run:  PYTHONPATH=src python benchmarks/serve_bench.py [--smoke] [--int8]
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro.core import AutotunePolicy, FixedPolicy, default_cache_path
from repro.models.graph_lm import GraphLMConfig, init_lm_params
from repro.runtime.engine import (EngineRequest, ProgramStepper,
                                  build_lm_serving, padded_len)
from repro.runtime.kv_cache import kv_page_bytes, pages_needed
from repro.tools.docgen import SERVING_OPS
from repro.tools.report import _fmt_assignment

# bump when the JSON record's shape changes incompatibly (BENCH_serve.json
# is a tracked trajectory — downstream tooling keys on this).
# v3: added the "load" section (trace-driven SLO goodput) and the
# engine summary's "self_heal" sub-record; every v2 section is unchanged.
# v4: added the "spec" section (speculative decoding: accept rate, decode
# tokens/s speculative vs baseline, token_exact) and the engine summary's
# "spec" sub-record; percentile dicts now carry "n_samples" and report
# empty windows as null instead of 0.0.
# v5: added the "sharded" section (tensor-parallel serving: decode tok/s
# and peak concurrent requests at TP=1 vs TP=2, token_exact).  Always
# present; ``{"enabled": false, "reason": ...}`` when not requested
# (--sharded) or when the process has a single device — the TP run needs
# XLA_FLAGS=--xla_force_host_platform_device_count (or real devices).
# v6: added the "overload" section (tier-aware scheduling vs tier-blind
# FIFO on a 2x-offered-load trace: per-policy load reports, preemption
# and tier-shed counts, high-tier SLO attainment under both policies).
SCHEMA_VERSION = 6
DEFAULT_JSON = "BENCH_serve.json"

# section -> required keys; ``validate_record`` (and CI, via --validate)
# checks the record's shape before it is uploaded as a trajectory artifact
REQUIRED_SECTIONS: Dict[str, Tuple[str, ...]] = {
    "config": ("smoke", "n_slots", "chunk", "model"),
    "engine": ("tokens_per_s", "latency_s", "ttft_s", "self_heal"),
    "unbatched": ("tokens_per_s",),
    "prefill_gap": ("max_gap_chunked_s", "gap_bounded"),
    "dispatch": ("call_us", "bind_us"),
    "paged": ("capacity", "prefix", "token_exact", "pool"),
    "paged_kv8": ("capacity", "token_exact", "pool"),
    "spec": ("spec_k", "draft_layers", "accept_rate", "decode_tok_s_spec",
             "decode_tok_s_base", "decode_speedup", "token_exact"),
    "load": ("slo", "trace", "overall", "tiers"),
    "overload": ("offered_x", "slo", "high_tier", "policies",
                 "high_tier_attainment", "tier_aware_wins"),
    "backend_sweep": (),
    "autotune": ("assignment",),
    "sharded": ("enabled",),
}

SMOKE_CFG = GraphLMConfig(vocab=61, d_model=32, n_layers=1, n_heads=4,
                          n_kv_heads=2, d_ff=64)
FULL_CFG = GraphLMConfig(vocab=128, d_model=64, n_layers=2, n_heads=4,
                         n_kv_heads=2, d_ff=128)


def _workload(cfg: GraphLMConfig, n_requests: int, max_new: int, seed: int):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n_requests):
        plen = int(rng.integers(2, 16))
        prompt = rng.integers(0, cfg.vocab, size=plen).astype(np.int32)
        out.append((prompt, max_new))
    return out


def _throughput(cfg, workload, *, n_slots, chunk, cache_cap, quantize,
                check_exact: bool) -> Dict[str, Any]:
    engine, ref = build_lm_serving(cfg, n_slots=n_slots, chunk=chunk,
                                   cache_cap=cache_cap, quantize=quantize)
    reqs = [EngineRequest(uid=i, prompt=p, max_new_tokens=m)
            for i, (p, m) in enumerate(workload)]
    # warm both Programs (compile outside the timed region)
    warm = EngineRequest(uid=-1, prompt=workload[0][0], max_new_tokens=2)
    engine.submit(warm)
    engine.run()
    engine.reset_metrics()                     # measure past the warmup

    for r in reqs:
        assert engine.submit(r)
    engine.run(max_ticks=100_000)
    eng_summary = engine.metrics.summary()

    # unbatched baseline: same requests, one at a time, one-shot prefill.
    # One fixed prefill shape (every prompt padded to the workload max) so
    # the timed loop measures execution, not per-length recompiles.
    ref_chunk = max(padded_len(len(p), chunk) for p, _ in workload)
    ref.generate(workload[0][0], 2, chunk=ref_chunk)       # warm
    t0 = time.perf_counter()
    ref_tokens = [ref.generate(p, m, chunk=ref_chunk) for p, m in workload]
    ref_wall = time.perf_counter() - t0
    ref_n = sum(len(t) for t in ref_tokens)

    if check_exact:
        for r, want in zip(reqs, ref_tokens):
            assert r.out_tokens == want, (
                f"engine diverged from reference on request {r.uid}: "
                f"{r.out_tokens} vs {want}")

    unbatched = {"tokens_out": ref_n, "wall_s": ref_wall,
                 "tokens_per_s": ref_n / ref_wall if ref_wall > 0 else 0.0}
    speedup = (eng_summary["tokens_per_s"] / unbatched["tokens_per_s"]
               if unbatched["tokens_per_s"] else 0.0)
    return {"engine": eng_summary, "unbatched": unbatched,
            "speedup": speedup, "token_exact": bool(check_exact),
            "backends": _serving_assignment(engine.stepper)}


def _gap_experiment(cfg, *, n_slots, chunk, cache_cap, long_prompt_len,
                    quantize, seed: int) -> Dict[str, Any]:
    """Max inter-token gap of an in-flight decode while a long prompt is
    admitted: chunked vs one-shot prefill, vs one full-prompt prefill."""
    rng = np.random.default_rng(seed)
    long_prompt = rng.integers(0, cfg.vocab, size=long_prompt_len).astype(np.int32)
    short_prompt = rng.integers(0, cfg.vocab, size=4).astype(np.int32)
    oneshot_chunk = padded_len(long_prompt_len, chunk)
    cap = max(cache_cap, oneshot_chunk + 40)

    def run_mode(mode_chunk: int):
        engine, _ = build_lm_serving(cfg, n_slots=n_slots, chunk=mode_chunk,
                                     cache_cap=cap, quantize=quantize)
        warm = EngineRequest(uid=-1, prompt=short_prompt, max_new_tokens=2)
        engine.submit(warm)
        engine.run()
        victim = EngineRequest(uid=0, prompt=short_prompt, max_new_tokens=24)
        engine.submit(victim)
        while victim.t_first is None:
            engine.step()
        for _ in range(2):          # victim is mid-decode
            engine.step()
        engine.submit(EngineRequest(uid=1, prompt=long_prompt,
                                    max_new_tokens=4))
        engine.run(max_ticks=10_000)
        return victim.max_gap_s, engine

    gap_chunked, _ = run_mode(chunk)
    gap_oneshot, eng1 = run_mode(oneshot_chunk)

    # one full-prompt prefill on the serving path: a single engine-shaped
    # prefill Program call covering the whole long prompt (already warm —
    # the one-shot engine above jitted exactly this shape)
    st = eng1.stepper
    tokens = np.zeros((n_slots, oneshot_chunk), np.int32)
    tokens[0, :long_prompt_len] = long_prompt
    start = np.zeros((n_slots,), np.int32)
    n_new = np.zeros((n_slots,), np.int32)
    n_new[0] = long_prompt_len
    st.prefill(tokens, start, n_new)           # warm cache-threading path
    t0 = time.perf_counter()
    st.prefill(tokens, start, n_new)
    full_prefill_s = time.perf_counter() - t0
    return {"chunk": chunk, "long_prompt_len": long_prompt_len,
            "max_gap_chunked_s": gap_chunked,
            "max_gap_oneshot_s": gap_oneshot,
            "full_prefill_s": full_prefill_s,
            "gap_bounded": bool(gap_chunked < full_prefill_s)}


def _serving_assignment(stepper: ProgramStepper) -> Dict[str, Any]:
    """The serving-op slice of the stepper's backend summary."""
    full = stepper.backend_summary()
    return {phase: {op: counts for op, counts in per_op.items()
                    if op in SERVING_OPS}
            for phase, per_op in full.items()}


def _step_rate(fn, tokens_per_call: int, reps: int) -> float:
    """Steady-state tokens/s of one stepper step function."""
    fn()                                   # warm (jit compile)
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    dt = time.perf_counter() - t0
    return tokens_per_call * reps / dt if dt > 0 else 0.0


def _backend_sweep(cfg, *, n_slots, chunk, cache_cap, reps: int,
                   params=None) -> Dict[str, Any]:
    """Prefill/decode step throughput with the serving ops pinned per
    backend, plus the resulting per-op assignments.  Non-serving ops keep
    the default xla-then-ref preference in every row, so the delta between
    rows is the serving ops' backends and nothing else."""
    params = params if params is not None else init_lm_params(cfg, 0)
    rows: Dict[str, Any] = {}
    prefs = {
        "ref": ("ref",),
        "xla": ("xla", "ref"),
        "pallas": ("pallas", "xla", "ref"),
        # split-KV decode first, so the row actually exercises it (plain
        # pallas would otherwise always win the preference order)
        "pallas_split": ("pallas_split", "pallas", "xla", "ref"),
    }
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab, size=(n_slots, chunk)).astype(np.int32)
    dec_tokens = tokens[:, :1]
    start = np.zeros((n_slots,), np.int32)
    pre_n = np.full((n_slots,), chunk, np.int32)
    dec_n = np.ones((n_slots,), np.int32)
    for label, pref in prefs.items():
        policy = FixedPolicy(per_op={op: pref for op in SERVING_OPS})
        st = ProgramStepper(cfg, params, n_slots=n_slots, chunk=chunk,
                            cache_cap=cache_cap, policy=policy)
        rows[label] = {
            "assignment": _serving_assignment(st),
            "prefill_tok_s": _step_rate(
                lambda: st.prefill(tokens, start, pre_n),
                n_slots * chunk, reps),
            "decode_tok_s": _step_rate(
                lambda: st.decode(dec_tokens, start, dec_n),
                n_slots, reps),
        }
    ref = rows["ref"]
    for row in rows.values():
        row["prefill_vs_ref"] = (row["prefill_tok_s"] / ref["prefill_tok_s"]
                                 if ref["prefill_tok_s"] else 0.0)
        row["decode_vs_ref"] = (row["decode_tok_s"] / ref["decode_tok_s"]
                                if ref["decode_tok_s"] else 0.0)
    return rows


def _autotune_report(cfg, *, n_slots, chunk, cache_cap, reps: int,
                     cache_path: Optional[str] = None,
                     params=None) -> Dict[str, Any]:
    """Compile the serving Programs under ``AutotunePolicy`` with the
    persistent on-disk cache, and report what it picked for the serving
    ops.  A second run of this benchmark on the same machine performs zero
    re-measurements (everything preloads from the cache)."""
    params = params if params is not None else init_lm_params(cfg, 0)
    path = cache_path or default_cache_path()
    pol = AutotunePolicy(reps=reps, cache_path=path)
    st = ProgramStepper(cfg, params, n_slots=n_slots, chunk=chunk,
                        cache_cap=cache_cap, policy=pol)
    return {
        "cache_path": path,
        "n_measured": pol.n_measured,
        "n_loaded": pol.n_loaded,
        "assignment": _serving_assignment(st),
    }


def _paged_experiment(cfg, *, n_slots, chunk, cache_cap, page_size,
                      quantize, seed: int) -> Dict[str, Any]:
    """The paged-KV-cache record: capacity at equal memory (max concurrent
    requests, dense vs paged), prefix-hit vs cold TTFT on a shared-prefix
    workload, token-exactness vs the dense reference, and pool stats.
    Report-only — wall-clock numbers are for trend inspection (this box
    has ~3x timing noise); the tick counts are deterministic."""
    rng = np.random.default_rng(seed)
    max_pages = -(-cache_cap // page_size)
    n_blocks = n_slots * max_pages          # same memory as the dense cache
    plen, max_new = 12, 6                   # the capacity workload shape
    per_req = pages_needed(plen, max_new, page_size)
    # one more slot than the pool can feed, so BLOCKS are what binds
    paged_slots = min(n_blocks // per_req + 1, 16)

    def peak_concurrency(engine, n_requests: int) -> int:
        for i in range(n_requests):
            p = rng.integers(0, cfg.vocab, size=plen).astype(np.int32)
            engine.submit(EngineRequest(uid=i, prompt=p,
                                        max_new_tokens=max_new))
        peak = 0
        while engine.has_work() and engine.tick < 10_000:
            engine.step()
            peak = max(peak, engine.sched.busy_slots)
        return peak

    dense_eng, _ = build_lm_serving(cfg, n_slots=n_slots, chunk=chunk,
                                    cache_cap=cache_cap, quantize=quantize)
    paged_eng, paged_ref = build_lm_serving(
        cfg, n_slots=paged_slots, chunk=chunk, cache_cap=cache_cap,
        paged=True, page_size=page_size, n_blocks=n_blocks,
        quantize=quantize)
    dense_peak = peak_concurrency(dense_eng, 2 * paged_slots)
    paged_peak = peak_concurrency(paged_eng, 2 * paged_slots)

    # prefix-hit vs cold TTFT: one long shared prefix, measured on the
    # SAME engine (cold request populates the prefix index, warm one hits)
    prefix_len = min(40, cache_cap - 8)
    prefix = rng.integers(0, cfg.vocab, size=prefix_len).astype(np.int32)

    def one_request(tail_len: int) -> EngineRequest:
        tail = rng.integers(0, cfg.vocab, size=tail_len).astype(np.int32)
        req = EngineRequest(uid=1000 + tail_len,
                            prompt=np.concatenate([prefix, tail]),
                            max_new_tokens=4)
        assert paged_eng.submit(req), req.dropped
        # max_ticks is an ABSOLUTE lifetime tick and this engine already
        # ran the capacity workload — budget relative to where it is now
        paged_eng.run(max_ticks=paged_eng.tick + 10_000)
        return req

    warmup = one_request(1)                 # compile + warm, also caches
    hits0 = paged_eng.stepper.pool.hit_tokens
    pool0 = paged_eng.stepper.pool
    # drop the cached prefix so the "cold" run really is cold: build a
    # fresh engine sharing nothing, then a hit run on the warmed engine
    cold_eng, _ = build_lm_serving(
        cfg, n_slots=paged_slots, chunk=chunk, cache_cap=cache_cap,
        paged=True, page_size=page_size, n_blocks=n_blocks,
        quantize=quantize)
    # warm prompt's FIRST token differs from the prefix's, so the pages it
    # registers can never prefix-hit the measured cold request
    warm_prompt = np.full(4, (int(prefix[0]) + 1) % cfg.vocab, np.int32)
    warm_req = EngineRequest(uid=-1, prompt=warm_prompt, max_new_tokens=2)
    cold_eng.submit(warm_req)
    cold_eng.run()                          # jit outside the timed request
    cold = EngineRequest(uid=1, prompt=np.concatenate(
        [prefix, rng.integers(0, cfg.vocab, size=2).astype(np.int32)]),
        max_new_tokens=4)
    assert cold_eng.submit(cold)
    cold_eng.run(max_ticks=cold_eng.tick + 10_000)
    hit = one_request(2)
    hit_tokens = paged_eng.stepper.pool.hit_tokens - hits0

    exact = (hit.out_tokens == paged_ref.generate(hit.prompt, 4)
             and warmup.out_tokens == paged_ref.generate(warmup.prompt, 4))
    cold_ticks = (cold.first_token_tick or 0) - cold.submit_tick
    hit_ticks = (hit.first_token_tick or 0) - hit.submit_tick
    page_b = kv_page_bytes(cfg.n_layers, cfg.n_kv_heads, cfg.d_head,
                           page_size)
    return {
        "kv_dtype": "float32",
        "page_size": page_size,
        "n_blocks": n_blocks,
        "memory_rows": n_blocks * page_size,
        "page_bytes": page_b,
        "pool_bytes": n_blocks * page_b,
        "capacity": {
            "dense_slots": n_slots,
            "dense_concurrent": dense_peak,
            "paged_slots": paged_slots,
            "paged_concurrent": paged_peak,
            "ratio": paged_peak / dense_peak if dense_peak else 0.0,
            "request_shape": {"prompt_len": plen, "max_new": max_new,
                              "pages_per_request": per_req},
        },
        "prefix": {
            "prefix_len": prefix_len,
            "hit_tokens": int(hit_tokens),
            "ttft_cold_s": cold.ttft_s,
            "ttft_hit_s": hit.ttft_s,
            "prefill_ticks_cold": cold_ticks,
            "prefill_ticks_hit": hit_ticks,
            "hit_faster": bool((hit.ttft_s or 0) < (cold.ttft_s or 0)),
        },
        "token_exact": bool(exact),
        "pool": pool0.stats(),
        "backends": _serving_assignment(paged_eng.stepper),
    }


def _paged_kv8_experiment(cfg, *, chunk, cache_cap, page_size, quantize,
                          seed: int, fp32_paged: Dict[str, Any]
                          ) -> Dict[str, Any]:
    """The quantized-cache record: an int8-paged engine given the SAME pool
    byte budget as the fp32-paged run. int8 pages are ~4x smaller, so the
    same bytes buy ~4x the blocks; the headline is peak concurrency at
    equal memory (acceptance bar: >= 1.8x). Token-exactness vs the fp32
    dense reference is checked on the three admission paths — cold,
    full-prefix hit, and CoW divergence into a shared partial tail page."""
    rng = np.random.default_rng(seed + 1)
    fp32_bytes = fp32_paged["pool_bytes"]
    page_b = kv_page_bytes(cfg.n_layers, cfg.n_kv_heads, cfg.d_head,
                           page_size, "int8")
    n_blocks = fp32_bytes // page_b         # equal device memory
    plen, max_new = 12, 6                   # same shape as the fp32 run
    per_req = pages_needed(plen, max_new, page_size)
    slots = min(n_blocks // per_req + 1, 16)
    engine, ref = build_lm_serving(
        cfg, n_slots=slots, chunk=chunk, cache_cap=cache_cap,
        paged=True, page_size=page_size, n_blocks=n_blocks,
        kv_dtype="int8", quantize=quantize)

    for i in range(2 * slots):
        p = rng.integers(0, cfg.vocab, size=plen).astype(np.int32)
        engine.submit(EngineRequest(uid=i, prompt=p, max_new_tokens=max_new))
    peak = 0
    while engine.has_work() and engine.tick < 20_000:
        engine.step()
        peak = max(peak, engine.sched.busy_slots)
    fp32_peak = fp32_paged["capacity"]["paged_concurrent"]

    def one_request(uid: int, prompt: np.ndarray) -> EngineRequest:
        req = EngineRequest(uid=uid, prompt=np.asarray(prompt, np.int32),
                            max_new_tokens=4)
        assert engine.submit(req), req.dropped
        engine.run(max_ticks=engine.tick + 10_000)
        return req

    pool = engine.stepper.pool
    prefix = rng.integers(0, cfg.vocab, size=14).astype(np.int32)
    cold = one_request(2001, prefix)        # registers full + partial pages
    hits0 = pool.hit_tokens
    hit = one_request(2002, np.concatenate(
        [prefix, rng.integers(0, cfg.vocab, size=3).astype(np.int32)]))
    hit_tokens = pool.hit_tokens - hits0
    # CoW divergence: replay the cold request's full token stream (prompt
    # plus written-back outputs) so its frozen partial tail page is claimed,
    # then one diverging token forces the append to copy that int8 page and
    # its scale row before writing
    cow0 = pool.cow_count
    cow_prompt = np.concatenate(
        [prefix, np.asarray(cold.out_tokens[:3], np.int32),
         np.asarray([(int(cold.out_tokens[3]) + 1) % cfg.vocab], np.int32)])
    cow = one_request(2003, cow_prompt)
    cow_copies = pool.cow_count - cow0

    exact = {
        "cold": bool(cold.out_tokens == ref.generate(cold.prompt, 4)),
        "prefix_hit": bool(hit.out_tokens == ref.generate(hit.prompt, 4)),
        "cow": bool(cow.out_tokens == ref.generate(cow_prompt, 4)),
    }
    exact["all"] = all(exact.values())
    return {
        "kv_dtype": "int8",
        "page_size": page_size,
        "n_blocks": n_blocks,
        "page_bytes": page_b,
        "pool_bytes": n_blocks * page_b,
        "fp32_pool_bytes": fp32_bytes,
        "capacity": {
            "paged_slots": slots,
            "paged_concurrent": peak,
            "fp32_paged_concurrent": fp32_peak,
            "equal_memory_vs_fp32_paged":
                peak / fp32_peak if fp32_peak else 0.0,
            "request_shape": {"prompt_len": plen, "max_new": max_new,
                              "pages_per_request": per_req},
        },
        "prefix": {"hit_tokens": int(hit_tokens),
                   "cow_copies": int(cow_copies)},
        "token_exact": exact,
        "pool": pool.stats(),
        "backends": _serving_assignment(engine.stepper),
    }


def _spec_experiment(cfg, *, n_slots, chunk, cache_cap, quantize,
                     seed: int, smoke: bool) -> Dict[str, Any]:
    """Speculative decoding on a decode-heavy workload: the SAME engine
    shape with and without greedy draft/verify speculation, scored on
    decode tokens/s (the engine metrics' decode-phase wall clock, prefill
    excluded on both sides so the ratio isolates the decode loop).

    The draft model is the early-exit self-speculative half of the target
    (``max(1, n_layers // 2)`` layers).  On the one-layer smoke model that
    degenerates to the full model — acceptance rate exactly 1.0 — which is
    precisely what makes the smoke number a dispatch-overhead measurement:
    every tick commits spec_k+1 tokens for two Program calls (one unrolled
    draft, one batched verify) where the baseline pays one call per token.
    The acceptance bar (>= 1.5x decode tokens/s in smoke) rides on that
    call-count ratio, not on kernel speed; smoke uses a wide K (the
    all-accept draft makes extra width free) and each engine's rate is
    the best of ``reps`` identical bursts, because a single burst on this
    box has enough scheduler noise to swamp the ratio.

    Token-exactness of the speculative engine vs the unbatched reference
    AND vs the non-speculative engine on every burst is recorded as
    ``token_exact`` (greedy speculation is lossless; False here is a bug,
    and report.spec_table renders it loudly)."""
    spec_k = 7 if smoke else 4
    draft_layers = max(1, cfg.n_layers // 2)
    max_new = 32
    n_requests = 12
    reps = 5 if smoke else 3
    rng = np.random.default_rng(seed + 7)
    workload = [(rng.integers(0, cfg.vocab,
                              size=int(rng.integers(2, 11))).astype(np.int32),
                 max_new) for _ in range(n_requests)]

    def run_one(k: int):
        """Best steady-state decode rate over ``reps`` bursts, the last
        burst's summary, and every burst's requests (outputs are
        deterministic, so all bursts must agree token-for-token)."""
        engine, ref = build_lm_serving(
            cfg, n_slots=n_slots, chunk=chunk, cache_cap=cache_cap,
            quantize=quantize, spec_k=k,
            draft_layers=draft_layers if k else None)
        warm = EngineRequest(uid=-1, prompt=workload[0][0], max_new_tokens=2)
        engine.submit(warm)
        engine.run()                       # compile outside the timed region
        best, summary, all_reqs = 0.0, None, []
        for rep in range(reps):
            engine.reset_metrics()
            reqs = [EngineRequest(uid=100 * rep + i, prompt=p,
                                  max_new_tokens=m)
                    for i, (p, m) in enumerate(workload)]
            for r in reqs:
                assert engine.submit(r), r.dropped
            engine.run(max_ticks=engine.tick + 100_000)
            summary = engine.metrics.summary()
            best = max(best, summary["spec"]["decode_tokens_per_s"])
            all_reqs.extend(reqs)
        return best, summary, all_reqs, ref

    base_rate, _, base_reqs, _ = run_one(0)
    spec_rate, spec_summary, spec_reqs, ref = run_one(spec_k)

    ref_chunk = max(padded_len(len(p), chunk) for p, _ in workload)
    oracle = [ref.generate(p, m, chunk=ref_chunk) for p, m in workload]
    exact = all(
        r.out_tokens == oracle[i % n_requests]
        for i, r in enumerate(spec_reqs))
    # and identical to the non-speculative engine on the same bursts —
    # speculation must be invisible in the tokens, not just close
    exact = exact and all(a.out_tokens == b.out_tokens
                          for a, b in zip(spec_reqs, base_reqs))

    sp = spec_summary["spec"]
    return {
        "spec_k": spec_k,
        "draft_layers": draft_layers,
        "n_layers": cfg.n_layers,
        "workload": {"n_requests": n_requests, "max_new": max_new,
                     "reps": reps},
        "spec_ticks": sp["spec_ticks"],
        "proposed": sp["proposed"],
        "accepted": sp["accepted"],
        "accept_rate": sp["accept_rate"],
        "decode_tok_s_spec": spec_rate,
        "decode_tok_s_base": base_rate,
        "decode_speedup": spec_rate / base_rate if base_rate else 0.0,
        "token_exact": bool(exact),
    }


def _load_experiment(cfg, *, n_slots, chunk, cache_cap, quantize,
                     seed: int, smoke: bool) -> Dict[str, Any]:
    """Trace-driven load: a seeded bursty trace (priority tiers + shared
    prefix populations) against a paged self-healing engine with bounded
    admission, scored for goodput under SLO (see repro.runtime.loadgen).
    The tick-denominated numbers (goodput counts, shed/drop, ttft/gap
    percentiles in ticks) are deterministic for a given seed; wall-second
    figures ride along for operators."""
    from repro.runtime.loadgen import (SLO, PrefixPopulation, TierSpec,
                                       TraceConfig, generate_trace, run_load)
    trace_cfg = TraceConfig(
        seed=seed,
        n_requests=24 if smoke else 96,
        vocab=cfg.vocab,
        mean_interarrival_ticks=3.0,
        arrival="gamma",
        burstiness=4.0,
        prompt_len_mean=8.0, prompt_len_sigma=0.5,
        prompt_len_max=max(16, cache_cap // 3),
        new_tokens_mean=5.0, new_tokens_sigma=0.5, new_tokens_max=10,
        tiers=(TierSpec("interactive", priority=1, weight=0.6,
                        deadline_ticks=600),
               TierSpec("batch", priority=0, weight=0.4)),
        prefix_populations=(PrefixPopulation("sys_prompt", prefix_len=8),),
        prefix_share_p=0.5)
    trace = generate_trace(trace_cfg)
    slo = SLO(ttft_ticks=60, gap_ticks=8)
    engine, _ = build_lm_serving(
        cfg, n_slots=n_slots, chunk=chunk, cache_cap=cache_cap,
        paged=True, page_size=8, quantize=quantize,
        max_queue=4 * n_slots, self_heal=True)
    # warm the Programs so wall-clock goodput measures steady state
    warm = EngineRequest(uid=-1, prompt=trace.requests[0].prompt,
                         max_new_tokens=2)
    engine.submit(warm)
    engine.run()
    engine.reset_metrics()
    return run_load(engine, trace, slo)


def _overload_experiment(cfg, *, n_slots, chunk, cache_cap, quantize,
                         seed: int, smoke: bool) -> Dict[str, Any]:
    """Tier-aware overload scheduling vs the tier-blind FIFO baseline.

    One seeded trace offered at ~2x the engine's drain rate (the
    interarrival is derived from the per-request tick cost, so "2x" holds
    across smoke/full shapes), replayed against the SAME engine shape
    under both policies:

    * ``tier_blind`` — priorities stripped at submit
      (``run_load(tier_blind=True)``); a full queue rejects arrivals
      regardless of tier and nothing is ever preempted;
    * ``tier_aware`` — the engine sheds the lowest queued tier to admit
      higher ones and preempts a running low-tier decode when the queue
      head would blow its TTFT budget (``slo_ttft_ticks``); paged
      victims resume from their surviving pages, so preemption costs
      pool capacity, not recompute.

    The pool is provisioned generously (blocks are NOT the bottleneck —
    slots are) so the section isolates the scheduling policy.  The
    headline is high-tier SLO attainment under each policy, measured
    against OFFERED requests (``n_slo_met / n_offered``), not finished
    ones: under overload the baseline's failure mode is shedding
    high-tier arrivals at the full queue, and a shed request certainly
    did not meet its SLO — per-finished attainment would hide exactly
    the behavior this section exists to measure.  The record is invalid
    (``validate_record``) unless tier-aware strictly wins."""
    from repro.runtime.loadgen import (SLO, TierSpec, TraceConfig,
                                       generate_trace, run_load)
    slo = SLO(ttft_ticks=12, gap_ticks=12)
    high_tier = "interactive"
    # per-request tick cost ~= prefill ticks + decode ticks; offered rate
    # is 2x the slot drain rate n_slots / cost
    prompt_mean, new_mean = 8.0, 6.0
    cost = (prompt_mean // chunk + 1) + new_mean
    offered_x = 2.0
    trace_cfg = TraceConfig(
        seed=seed + 3,
        n_requests=32 if smoke else 96,
        vocab=cfg.vocab,
        mean_interarrival_ticks=cost / (offered_x * n_slots),
        arrival="gamma",
        burstiness=4.0,
        prompt_len_mean=prompt_mean, prompt_len_sigma=0.4,
        prompt_len_max=16,
        # a fat decode tail (sigma 0.8, max 24): the long low-tier decodes
        # that hold slots while a high-tier head's TTFT budget burns are
        # what give preemption something to do
        new_tokens_mean=new_mean, new_tokens_sigma=0.8, new_tokens_max=24,
        tiers=(TierSpec(high_tier, priority=1, weight=0.35,
                        deadline_ticks=400),
               TierSpec("batch", priority=0, weight=0.65)))
    trace = generate_trace(trace_cfg)
    page_size = 8
    # generous pool: every slot AND every queue entry could hold a
    # worst-case request's pages at once
    n_blocks = (n_slots + 2 * n_slots) * pages_needed(
        trace_cfg.prompt_len_max, trace_cfg.new_tokens_max, page_size)

    def run_policy(tier_aware: bool) -> Dict[str, Any]:
        engine, _ = build_lm_serving(
            cfg, n_slots=n_slots, chunk=chunk, cache_cap=cache_cap,
            paged=True, page_size=page_size, n_blocks=n_blocks,
            quantize=quantize, max_queue=2 * n_slots, self_heal=True,
            tier_aware=tier_aware,
            slo_ttft_ticks=slo.ttft_ticks if tier_aware else None)
        warm = EngineRequest(uid=-1, prompt=trace.requests[0].prompt,
                             max_new_tokens=2)
        engine.submit(warm)
        engine.run()
        engine.reset_metrics()
        report = run_load(engine, trace, slo, tier_blind=not tier_aware)
        return {"report": report,
                "n_preempted": engine.metrics.n_preempted,
                "n_tier_shed": engine.metrics.n_tier_shed}

    blind = run_policy(False)
    aware = run_policy(True)

    def att(pol: Dict[str, Any]) -> Optional[float]:
        tr = pol["report"]["tiers"][high_tier]
        return tr["n_slo_met"] / tr["n_offered"] if tr["n_offered"] else None

    return {
        "offered_x": offered_x,
        "slo": {"ttft_ticks": slo.ttft_ticks, "gap_ticks": slo.gap_ticks},
        "high_tier": high_tier,
        "trace": {"digest": trace.digest(),
                  "n_requests": trace_cfg.n_requests,
                  "mean_interarrival_ticks":
                      trace_cfg.mean_interarrival_ticks},
        "policies": {"tier_blind": blind, "tier_aware": aware},
        "high_tier_attainment": {"tier_blind": att(blind),
                                 "tier_aware": att(aware)},
        "tier_aware_wins": bool((att(aware) or 0.0) > (att(blind) or 0.0)),
    }


def _sharded_experiment(cfg, *, chunk, cache_cap, seed: int,
                        smoke: bool, tp: int = 2) -> Dict[str, Any]:
    """Tensor-parallel serving: the SAME paged engine shape at TP=1 and
    TP=tp, scored on decode tokens/s and peak concurrent requests, with
    token identity between the two checked on every request (the tp
    attention backends promise bitwise-exact serving — False here is a
    bug, and report.sharded_table renders it loudly).

    On this harness's forced host devices the TP=2 number measures
    dispatch/collective overhead, not kernel speedup (the "devices" are
    one CPU); the record exists so the trajectory catches regressions in
    the multi-device path, and so real-accelerator runs drop in with the
    same schema."""
    import jax
    n_dev = len(jax.devices())
    if n_dev < tp:
        return {"enabled": False,
                "reason": f"needs {tp} devices, have {n_dev} (set "
                          f"XLA_FLAGS=--xla_force_host_platform_"
                          f"device_count or run on real devices)"}

    rng = np.random.default_rng(seed + 11)
    n_requests, max_new, plen = (8, 8, 10) if smoke else (16, 16, 12)
    workload = [(rng.integers(0, cfg.vocab, size=plen).astype(np.int32),
                 max_new) for _ in range(n_requests)]
    page_size = 8
    n_blocks = n_requests * pages_needed(plen, max_new, page_size)

    def run_one(tp_degree: Optional[int]):
        engine, _ = build_lm_serving(
            cfg, n_slots=min(n_requests, 8), chunk=chunk,
            cache_cap=cache_cap, paged=True, page_size=page_size,
            n_blocks=n_blocks, tp=tp_degree)
        warm = EngineRequest(uid=-1, prompt=workload[0][0], max_new_tokens=2)
        engine.submit(warm)
        engine.run()                   # compile outside the timed region
        engine.reset_metrics()
        reqs = [EngineRequest(uid=i, prompt=p, max_new_tokens=m)
                for i, (p, m) in enumerate(workload)]
        for r in reqs:
            assert engine.submit(r), r.dropped
        peak = 0
        while engine.has_work() and engine.tick < 100_000:
            engine.step()
            peak = max(peak, engine.sched.busy_slots)
        summary = engine.metrics.summary()
        assignment = _serving_assignment(engine.stepper)
        return reqs, {"decode_tok_s": summary["spec"]["decode_tokens_per_s"],
                      "tokens_per_s": summary["tokens_per_s"],
                      "peak_concurrent": peak,
                      "backends": assignment}

    base_reqs, tp1 = run_one(None)
    tp_reqs, tpn = run_one(tp)
    exact = all(a.out_tokens == b.out_tokens and a.done and b.done
                for a, b in zip(base_reqs, tp_reqs))
    return {"enabled": True, "tp": tp, "devices": n_dev,
            "workload": {"n_requests": n_requests, "max_new": max_new,
                         "prompt_len": plen},
            "tp1": tp1, f"tp{tp}": tpn,
            "token_exact": bool(exact)}


def _dispatch_overhead(cfg, *, n_slots, chunk, cache_cap, reps: int = 100
                       ) -> Dict[str, float]:
    """µs/call of the kwargs Program path vs the bind() fast path on the
    decode step (same computation; the delta is pure dispatch)."""
    import jax
    engine, _ = build_lm_serving(cfg, n_slots=n_slots, chunk=chunk,
                                 cache_cap=cache_cap)
    st = engine.stepper
    toks = np.zeros((n_slots, 1), np.int32)
    start = np.zeros((n_slots,), np.int32)
    n_new = np.ones((n_slots,), np.int32)
    caches = {k: st.caches[k] for k in sorted(st.caches)}
    kwargs = {"tokens": toks, "start": start, "n_new": n_new, **caches}
    bound = st.decode_program.bind("tokens", "start", "n_new", *sorted(caches))
    args = (toks, start, n_new, *[caches[k] for k in sorted(caches)])

    def timed(fn) -> float:
        jax.block_until_ready(fn())      # warm
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn()
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / reps * 1e6

    call_us = timed(lambda: st.decode_program(**kwargs))
    bind_us = timed(lambda: bound(*args))
    return {"call_us": call_us, "bind_us": bind_us,
            "saved_us": call_us - bind_us}


def run(*, smoke: bool = False, quantize: Optional[str] = None,
        n_slots: Optional[int] = None, chunk: int = 8,
        seed: int = 0, autotune_cache: Optional[str] = None,
        sharded: bool = False) -> Dict[str, Any]:
    cfg = SMOKE_CFG if smoke else FULL_CFG
    slots = n_slots or (2 if smoke else 4)
    cache_cap = 64 if smoke else 128
    n_requests = 6 if smoke else 16
    max_new = 6 if smoke else 24
    long_prompt = 64 if smoke else 384

    workload = _workload(cfg, n_requests, max_new, seed)
    result: Dict[str, Any] = {
        "schema_version": SCHEMA_VERSION,
        "config": {"smoke": smoke, "quantize": quantize, "n_slots": slots,
                   "chunk": chunk, "cache_cap": cache_cap,
                   "n_requests": n_requests, "max_new_tokens": max_new,
                   "model": {"vocab": cfg.vocab, "d_model": cfg.d_model,
                             "n_layers": cfg.n_layers}},
    }
    result.update(_throughput(cfg, workload, n_slots=slots, chunk=chunk,
                              cache_cap=cache_cap, quantize=quantize,
                              check_exact=True))
    result["prefill_gap"] = _gap_experiment(
        cfg, n_slots=slots, chunk=chunk, cache_cap=cache_cap,
        long_prompt_len=long_prompt, quantize=quantize, seed=seed)
    result["dispatch"] = _dispatch_overhead(
        cfg, n_slots=slots, chunk=chunk, cache_cap=cache_cap,
        reps=50 if smoke else 200)
    result["paged"] = _paged_experiment(
        cfg, n_slots=slots, chunk=chunk, cache_cap=cache_cap,
        page_size=8, quantize=quantize, seed=seed)
    result["paged_kv8"] = _paged_kv8_experiment(
        cfg, chunk=chunk, cache_cap=cache_cap, page_size=8,
        quantize=quantize, seed=seed, fp32_paged=result["paged"])
    result["spec"] = _spec_experiment(
        cfg, n_slots=slots, chunk=chunk, cache_cap=cache_cap,
        quantize=quantize, seed=seed, smoke=smoke)
    result["load"] = _load_experiment(
        cfg, n_slots=slots, chunk=chunk, cache_cap=cache_cap,
        quantize=quantize, seed=seed, smoke=smoke)
    result["overload"] = _overload_experiment(
        cfg, n_slots=slots, chunk=chunk, cache_cap=cache_cap,
        quantize=quantize, seed=seed, smoke=smoke)
    result["sharded"] = (_sharded_experiment(
        cfg, chunk=chunk, cache_cap=cache_cap, seed=seed, smoke=smoke)
        if sharded else
        {"enabled": False, "reason": "not requested (--sharded)"})
    params = init_lm_params(cfg, 0)
    result["backend_sweep"] = _backend_sweep(
        cfg, n_slots=slots, chunk=chunk, cache_cap=cache_cap,
        reps=5 if smoke else 20, params=params)
    result["autotune"] = _autotune_report(
        cfg, n_slots=slots, chunk=chunk, cache_cap=cache_cap,
        reps=2 if smoke else 3, cache_path=autotune_cache, params=params)
    return result


def validate_record(rec: Dict[str, Any]) -> List[str]:
    """Schema check for a BENCH_serve.json record; returns the list of
    problems (empty = valid).  CI runs this (``--validate``) before the
    record is uploaded as a trajectory artifact, so a benchmark refactor
    that silently drops a section fails the build instead of poisoning
    the trend history."""
    problems: List[str] = []
    if rec.get("schema_version") != SCHEMA_VERSION:
        problems.append(f"schema_version {rec.get('schema_version')!r} "
                        f"!= {SCHEMA_VERSION}")
    for section, keys in REQUIRED_SECTIONS.items():
        body = rec.get(section)
        if not isinstance(body, dict):
            problems.append(f"missing section {section!r}")
            continue
        for k in keys:
            if k not in body:
                problems.append(f"section {section!r} missing key {k!r}")

    def check_pct(where: str, d: Any) -> None:
        # v4 percentile contract: every percentile dict says how many
        # samples it saw, and "no data" is null on every quantile — an
        # empty window must never score as a perfect 0.0
        if not isinstance(d, dict):
            problems.append(f"{where} is not a percentile dict")
            return
        if "n_samples" not in d:
            problems.append(f"{where} missing 'n_samples'")
            return
        empty = d["n_samples"] == 0
        for q in ("p50", "p95", "p99"):
            if q not in d:
                problems.append(f"{where} missing {q!r}")
            elif empty and d[q] is not None:
                problems.append(f"{where}.{q} is {d[q]!r} on an empty "
                                "window (must be null)")
            elif not empty and d[q] is None:
                problems.append(f"{where}.{q} is null despite "
                                f"{d['n_samples']} samples")

    eng = rec.get("engine")
    if isinstance(eng, dict):
        for k in ("latency_s", "ttft_s"):
            if k in eng:
                check_pct(f"engine.{k}", eng[k])
    spec = rec.get("spec")
    if isinstance(spec, dict):
        rate = spec.get("accept_rate")
        if not (isinstance(rate, (int, float)) and 0.0 <= rate <= 1.0):
            problems.append(f"spec.accept_rate {rate!r} outside [0, 1]")
        if not isinstance(spec.get("token_exact"), bool):
            problems.append("spec.token_exact is not a bool")
        base = spec.get("decode_tok_s_base")
        fast = spec.get("decode_tok_s_spec")
        ratio = spec.get("decode_speedup")
        if (isinstance(base, (int, float)) and base > 0
                and isinstance(fast, (int, float))
                and isinstance(ratio, (int, float))
                and abs(ratio - fast / base) > 1e-6 * max(1.0, ratio)):
            problems.append(f"spec.decode_speedup {ratio!r} inconsistent "
                            f"with {fast!r} / {base!r}")
    sh = rec.get("sharded")
    if isinstance(sh, dict):
        if sh.get("enabled") is True:
            tp = sh.get("tp")
            for key in ("tp", "devices", "tp1", f"tp{tp}", "token_exact"):
                if key not in sh:
                    problems.append(f"sharded (enabled) missing key {key!r}")
            for side in ("tp1", f"tp{tp}"):
                body = sh.get(side)
                if isinstance(body, dict):
                    for k in ("decode_tok_s", "peak_concurrent"):
                        if k not in body:
                            problems.append(
                                f"sharded.{side} missing key {k!r}")
                elif side in sh:
                    problems.append(f"sharded.{side} is not a dict")
            if not isinstance(sh.get("token_exact"), bool):
                problems.append("sharded.token_exact is not a bool")
        elif sh.get("enabled") is False:
            if "reason" not in sh:
                problems.append("sharded (disabled) missing 'reason'")
        else:
            problems.append(f"sharded.enabled {sh.get('enabled')!r} "
                            "is not a bool")
    load = rec.get("load")
    if isinstance(load, dict):
        ov = load.get("overall", {})
        for k in ("n_offered", "n_finished", "n_shed", "n_dropped",
                  "n_slo_met", "goodput_requests_per_s", "ttft_ticks",
                  "gap_ticks"):
            if k not in ov:
                problems.append(f"load.overall missing key {k!r}")
        for k in ("ttft_ticks", "gap_ticks"):
            if k in ov:
                check_pct(f"load.overall.{k}", ov[k])
        accounted = sum(ov.get(k, 0) for k in
                        ("n_finished", "n_shed", "n_dropped", "n_incomplete"))
        if accounted != ov.get("n_offered"):
            problems.append("load.overall conservation violated: "
                            f"{accounted} accounted vs "
                            f"{ov.get('n_offered')} offered")
    ovl = rec.get("overload")
    if isinstance(ovl, dict) and isinstance(ovl.get("policies"), dict):
        for policy in ("tier_blind", "tier_aware"):
            pol = ovl["policies"].get(policy)
            if not isinstance(pol, dict):
                problems.append(f"overload.policies missing {policy!r}")
                continue
            for k in ("report", "n_preempted", "n_tier_shed"):
                if k not in pol:
                    problems.append(f"overload.{policy} missing key {k!r}")
        # the headline claim is part of the schema: a record where
        # tier-aware scheduling does NOT strictly beat the tier-blind
        # baseline on high-tier SLO attainment is a regression, and it
        # fails validation instead of landing in the trend history
        att = ovl.get("high_tier_attainment", {})
        aware, blind = att.get("tier_aware"), att.get("tier_blind")
        if aware is None or not aware > (blind or 0.0):
            problems.append(
                f"overload: tier-aware high-tier attainment {aware!r} does "
                f"not strictly beat tier-blind {blind!r}")
        pre = ovl["policies"].get("tier_blind", {})
        if pre.get("n_preempted") or pre.get("n_tier_shed"):
            problems.append("overload: tier-blind baseline preempted or "
                            "tier-shed (it must do neither)")
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI configuration (tiny model, n_slots=2)")
    ap.add_argument("--int8", action="store_true",
                    help="serve int8-quantized Programs")
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--chunk", type=int, default=8)
    ap.add_argument("--sharded", action="store_true",
                    help="run the tensor-parallel (TP=1 vs TP=2) serving "
                         "comparison; needs >= 2 devices (CI forces host "
                         "devices via XLA_FLAGS)")
    ap.add_argument("--autotune-cache", metavar="PATH", default=None,
                    help="persistent autotune cache file (default: "
                         "ORPHEUS_AUTOTUNE_CACHE or ~/.cache/orpheus)")
    ap.add_argument("--json", metavar="PATH", nargs="?", const=DEFAULT_JSON,
                    help="write the schema-versioned JSON record here "
                         f"instead of stdout (bare --json: {DEFAULT_JSON} "
                         "at the repo root)")
    ap.add_argument("--validate", metavar="PATH", default=None,
                    help="validate an existing JSON record against the "
                         "current schema and exit (no benchmark run)")
    args = ap.parse_args(argv)

    if args.validate is not None:
        with open(args.validate) as f:
            rec = json.load(f)
        problems = validate_record(rec)
        for p in problems:
            print(f"INVALID: {p}")
        if not problems:
            print(f"# {args.validate}: valid schema v{SCHEMA_VERSION}")
        return 1 if problems else 0

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    rec = run(smoke=args.smoke, quantize="int8" if args.int8 else None,
              n_slots=args.slots, chunk=args.chunk,
              autotune_cache=args.autotune_cache, sharded=args.sharded)
    eng, unb = rec["engine"], rec["unbatched"]
    gap = rec["prefill_gap"]

    # empty percentile windows are null in the record (schema v4); render
    # them as an em dash instead of crashing the format spec
    def _ms(x: Optional[float]) -> str:
        return "—" if x is None else f"{x*1e3:.0f}ms"

    def _ticks(x: Optional[float]) -> str:
        return "—" if x is None else f"{x:.0f}t"

    print(f"# engine  : {eng['tokens_per_s']:,.0f} tok/s "
          f"(busy {eng['busy_slot_fraction']:.0%}, "
          f"p50 {_ms(eng['latency_s']['p50'])}, "
          f"p95 {_ms(eng['latency_s']['p95'])}, "
          f"ttft p50 {_ms(eng['ttft_s']['p50'])})")
    print(f"# unbatched: {unb['tokens_per_s']:,.0f} tok/s -> "
          f"speedup {rec['speedup']:.2f}x")
    print(f"# prefill gap: chunked {gap['max_gap_chunked_s']*1e3:.1f}ms vs "
          f"one-shot {gap['max_gap_oneshot_s']*1e3:.1f}ms "
          f"(full prefill {gap['full_prefill_s']*1e3:.1f}ms, "
          f"bounded={gap['gap_bounded']})")
    print(f"# dispatch: call {rec['dispatch']['call_us']:.0f}us vs "
          f"bind {rec['dispatch']['bind_us']:.0f}us per step")
    pg = rec["paged"]
    cap_r, pre = pg["capacity"], pg["prefix"]
    print(f"# paged   : page {pg['page_size']} x {pg['n_blocks']} blocks "
          f"(= dense memory); concurrent {cap_r['paged_concurrent']} vs "
          f"dense {cap_r['dense_concurrent']} ({cap_r['ratio']:.1f}x); "
          f"ttft hit {(pre['ttft_hit_s'] or 0)*1e3:.1f}ms vs cold "
          f"{(pre['ttft_cold_s'] or 0)*1e3:.1f}ms "
          f"({pre['prefill_ticks_hit']} vs {pre['prefill_ticks_cold']} "
          f"prefill ticks); exact={pg['token_exact']}")
    k8 = rec["paged_kv8"]
    k8c = k8["capacity"]
    print(f"# paged kv8: page {k8['page_bytes']}B x {k8['n_blocks']} blocks "
          f"(= fp32 pool bytes); concurrent {k8c['paged_concurrent']} vs "
          f"fp32 paged {k8c['fp32_paged_concurrent']} "
          f"({k8c['equal_memory_vs_fp32_paged']:.1f}x at equal memory); "
          f"cow copies {k8['prefix']['cow_copies']}; "
          f"exact={k8['token_exact']['all']}")
    sp = rec["spec"]
    print(f"# spec    : K={sp['spec_k']}, draft {sp['draft_layers']}/"
          f"{sp['n_layers']} layers; accept {sp['accept_rate']:.0%}; "
          f"decode {sp['decode_tok_s_spec']:,.0f} tok/s vs base "
          f"{sp['decode_tok_s_base']:,.0f} ({sp['decode_speedup']:.2f}x); "
          f"exact={sp['token_exact']}")
    sh = rec["sharded"]
    if sh["enabled"]:
        tpk = f"tp{sh['tp']}"
        print(f"# sharded : TP={sh['tp']} on {sh['devices']} devices; "
              f"decode {sh[tpk]['decode_tok_s']:,.0f} tok/s vs TP=1 "
              f"{sh['tp1']['decode_tok_s']:,.0f}; peak concurrent "
              f"{sh[tpk]['peak_concurrent']} vs {sh['tp1']['peak_concurrent']}; "
              f"exact={sh['token_exact']}")
    else:
        print(f"# sharded : disabled ({sh['reason']})")
    ld = rec["load"]
    ov = ld["overall"]
    print(f"# load    : {ov['n_offered']} offered -> "
          f"{ov['n_finished']} finished, {ov['n_shed']} shed, "
          f"{ov['n_dropped']} dropped; "
          f"{ov['n_slo_met']} met SLO (ttft<={ld['slo']['ttft_ticks']}t, "
          f"gap<={ld['slo']['gap_ticks']}t) -> "
          f"{ov['goodput_requests_per_s']:.1f} req/s goodput; "
          f"ttft p99 {_ticks(ov['ttft_ticks']['p99'])}, "
          f"gap p99 {_ticks(ov['gap_ticks']['p99'])}")
    ovl = rec["overload"]
    att = ovl["high_tier_attainment"]

    def _pct_or_dash(x: Optional[float]) -> str:
        return "—" if x is None else f"{x:.0%}"

    aw = ovl["policies"]["tier_aware"]
    print(f"# overload: {ovl['offered_x']:.0f}x offered load; "
          f"{ovl['high_tier']!r} SLO attainment tier-aware "
          f"{_pct_or_dash(att['tier_aware'])} vs tier-blind "
          f"{_pct_or_dash(att['tier_blind'])} "
          f"(preempted {aw['n_preempted']}, tier-shed {aw['n_tier_shed']}; "
          f"wins={ovl['tier_aware_wins']})")
    for label, row in rec["backend_sweep"].items():
        print(f"# sweep[{label:>6}]: prefill {row['prefill_tok_s']:,.0f} tok/s "
              f"({row['prefill_vs_ref']:.2f}x ref), "
              f"decode {row['decode_tok_s']:,.0f} tok/s "
              f"({row['decode_vs_ref']:.2f}x ref)")
    at = rec["autotune"]
    print(f"# autotune: measured {at['n_measured']} sigs "
          f"(+{at['n_loaded']} from cache) -> "
          f"{_fmt_assignment(at['assignment'])}")
    payload = json.dumps(rec, indent=1, sort_keys=True)
    if args.json:
        with open(args.json, "w") as f:
            f.write(payload)
        print(f"# wrote {args.json}")
    else:
        print(payload)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
