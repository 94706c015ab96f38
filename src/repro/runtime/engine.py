"""Program-backed serving engine: async request scheduling, chunked
prefill, per-token streaming.

This is where the repo's two halves meet: the staged compilation pipeline
(``compile()`` → :class:`~repro.core.program.Program`) becomes the serving
hot path.  Both engine steps are compiled Programs over the GraphIR LM
(:mod:`repro.models.graph_lm`) — so backend selection policies, int8
quantization and the persistent autotune cache all apply to sustained
traffic, not just offline evaluation:

* decode Program — tokens (B, 1) + caches → next-token logits, one call
  per engine decode tick over the whole fixed slot batch;
* prefill Program — tokens (B, chunk) + caches → per-position logits; long
  prompts are split into fixed-size chunks *interleaved with decode ticks*
  so a newly admitted long prompt never stalls in-flight decodes for more
  than ~one chunk (the bounded inter-token gap serve_bench measures).

Scheduling is deterministic and tick-based (wall-clock only feeds
metrics): :class:`~repro.runtime.batching.SlotScheduler` supplies priority
FIFO admission with bounded-queue admission control; per-request deadlines
(in ticks) drop expired work from the queue and from slots.  Tokens stream
to the caller via ``on_token`` callbacks the moment they are decoded;
:class:`AsyncEngine` wraps that into ``async for`` iteration.

Exactness contract: under greedy decoding the engine's outputs are
token-exact against :class:`UnbatchedReference` — a no-batching loop over
B=1 Programs compiled from the same graphs — for both fp32 and int8
Programs.  For int8 this requires every Program variant to share one set
of calibrated activation scales (see :func:`build_lm_serving`), because
dynamic per-batch scales would make a request's tokens depend on its
batch neighbours.

Self-healing (``self_heal=True``): every tick's Program call runs under
the :mod:`repro.ft` watchdogs — a :class:`~repro.ft.watchdog.HangDetector`
deadline (``hang_timeout``) and a :class:`~repro.ft.watchdog.StepWatchdog`
straggler tracker.  A tick that raises, or that overruns the hang
deadline, is DISCARDED: the engine restores the block pool to the
checkpoint taken at the start of the tick (:meth:`Engine.checkpoint` —
per-slot prompt + generated tokens + committed row count + block table,
plus a :meth:`~repro.runtime.kv_cache.BlockPool.snapshot`), tears the
slots down, and requeues every in-flight request at its original queue
position.  Resume is PAGE-LEVEL on every stepper: a requeued request
keeps every committed KV row it had — the paged stepper keeps its
sequence and block tables (int8 scale sidecars live in the same
block-id-indexed arrays, so they survive with their pages), and the
dense stepper keeps its per-slot cache rows, relocating them when the
request is re-admitted to a different slot — so prefill fast-forwards
past everything already computed and only the failed tick's token
position is re-executed.  The exactness contract extends across
recovery: greedy output after a crash or hang is token-identical to an
uninterrupted run, and no token is ever re-emitted to a streaming
callback (``tests/test_fault_injection.py``).

Tier-aware overload control (``tier_aware=True``): admission shedding
and preemption become scheduling decisions driven by request priority
(the loadgen's :class:`~repro.runtime.loadgen.TierSpec` tiers).  A full
queue sheds the lowest-priority queued request to make room for a
higher-priority arrival instead of turning the arrival away, and when
the highest-priority queued request is about to blow its TTFT budget
(``slo_ttft_ticks`` and/or its deadline) while every slot is busy, the
engine preempts the lowest-priority running slot.  A preempted request
requeues at its original position and resumes through the page-level
path above — preemption costs pages (they stay reserved), not
recompute.
"""

from __future__ import annotations

import asyncio
import time
from contextlib import nullcontext
from dataclasses import dataclass, field, replace
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.program import compile
from repro.core.selector import BackendPolicy, FixedPolicy
from repro.ft.coordinator import Coordinator
from repro.ft.watchdog import HangDetector, StepWatchdog
from repro.models.graph_lm import (GraphLMConfig, build_decode_graph,
                                   build_draft_graph,
                                   build_paged_decode_graph,
                                   build_paged_prefill_graph,
                                   build_paged_verify_graph,
                                   build_paged_verify_seq_graph,
                                   build_prefill_graph,
                                   build_spec_commit_graph,
                                   build_verify_graph,
                                   expand_spec_ranges, init_cache_inputs,
                                   init_lm_params, init_paged_cache_inputs)
from repro.runtime.batching import SlotScheduler
from repro.runtime.kv_cache import BlockPool, kv_page_bytes

__all__ = [
    "EngineRequest", "EngineMetrics", "Engine", "AsyncEngine",
    "ProgramStepper", "PagedProgramStepper", "UnbatchedReference",
    "build_lm_serving", "padded_len",
    "EngineCheckpoint", "CheckpointSlot", "TickFailure",
]


def padded_len(n: int, chunk: int) -> int:
    """Prompt length rounded up to a whole number of prefill chunks."""
    return -(-max(n, 1) // chunk) * chunk


# --------------------------------------------------------------------------- #
# Requests and metrics
# --------------------------------------------------------------------------- #

@dataclass
class EngineRequest:
    """One generation request.  Terminal states are mutually exclusive:
    ``done`` (finished normally) or ``dropped`` (reason string — admission
    rejection or deadline expiry); partial output survives a drop."""

    uid: int
    prompt: np.ndarray                      # (prompt_len,) int32
    max_new_tokens: int
    priority: int = 0
    tier: Optional[str] = None              # workload tier label (loadgen)
    deadline_tick: Optional[int] = None     # absolute engine tick to finish by
    on_token: Optional[Callable[["EngineRequest", int], None]] = None
    on_finish: Optional[Callable[["EngineRequest"], None]] = None

    out_tokens: List[int] = field(default_factory=list)
    done: bool = False
    dropped: Optional[str] = None
    submit_tick: int = -1
    first_token_tick: Optional[int] = None
    finish_tick: Optional[int] = None
    n_requeues: int = 0                     # times we were requeued
    #                                         (recovery or tier preemption)
    t_submit: float = 0.0
    t_first: Optional[float] = None
    t_done: Optional[float] = None
    max_gap_s: float = 0.0                  # max wall gap between our tokens
    max_gap_ticks: int = 0                  # same, in deterministic ticks
    _t_last_token: Optional[float] = None
    _last_token_tick: Optional[int] = None

    @property
    def latency_s(self) -> Optional[float]:
        return None if self.t_done is None else self.t_done - self.t_submit

    @property
    def ttft_s(self) -> Optional[float]:
        return None if self.t_first is None else self.t_first - self.t_submit

    @property
    def ttft_ticks(self) -> Optional[int]:
        """Deterministic TTFT: engine ticks from submit to first token.
        A prefix hit shrinks this (prefill fast-forwards past the reused
        rows), which is how the paged cache's latency win is asserted
        without wall-clock noise."""
        return (None if self.first_token_tick is None
                else self.first_token_tick - self.submit_tick)


def _pct(xs: Sequence[float], q: float) -> Optional[float]:
    """Percentile of a sample list; ``None`` for an empty window.  A run
    with zero finished requests has NO latency data — serializing that as
    0.0 would report a perfect p99, so "no data" is ``null`` in the JSON
    record and rendered as "—" by ``repro.tools.report``.  Single-sample
    and all-equal windows return that value for every q (linear
    interpolation over one distinct point) — edge cases pinned by
    ``tests/test_engine_metrics.py``.
    """
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else None


def _pct_dict(xs: Sequence[float]) -> Dict[str, Any]:
    """p50/p95/p99 plus ``n_samples`` so a consumer can tell "fast" from
    "no data" (percentiles are ``None`` iff ``n_samples == 0``)."""
    return {"p50": _pct(xs, 50), "p95": _pct(xs, 95), "p99": _pct(xs, 99),
            "n_samples": len(xs)}


@dataclass
class EngineMetrics:
    """Aggregated serving metrics — the record ``serve_bench`` emits as
    JSON and ``repro.tools.report.serving_table`` renders."""

    n_finished: int = 0
    n_dropped: int = 0
    n_rejected: int = 0
    ticks: int = 0
    decode_ticks: int = 0
    prefill_ticks: int = 0
    busy_slot_ticks: int = 0    # slots doing real work, summed over ticks
    n_slots: int = 0
    tokens_out: int = 0
    wall_s: float = 0.0
    latencies_s: List[float] = field(default_factory=list)
    ttfts_s: List[float] = field(default_factory=list)
    max_intertoken_gap_s: float = 0.0
    # self-healing counters (all zero when self_heal is off)
    failed_ticks: int = 0       # discarded ticks (crash + hang)
    n_crash_failures: int = 0
    n_hang_failures: int = 0
    n_recoveries: int = 0
    requeued_requests: int = 0  # slot preemptions summed over recoveries
    straggler_ticks: int = 0    # StepWatchdog rolling-median flags
    recovered_rows: int = 0     # KV rows resumed from surviving state
    #                             (pages / dense slot rows) instead of
    #                             being re-prefilled after a requeue
    # tier-aware overload counters (all zero when tier_aware is off)
    n_preempted: int = 0        # running low-tier slots preempted for
    #                             a high-tier request at TTFT risk
    n_tier_shed: int = 0        # queued low-tier requests shed to make
    #                             room for a higher-tier arrival
    # speculative decoding (all zero when spec_k == 0)
    spec_ticks: int = 0         # draft+verify ticks (counted in decode_ticks)
    spec_proposed: int = 0      # draft tokens offered to verification
    spec_accepted: int = 0      # draft tokens the target model agreed with
    # decode-phase throughput: tokens emitted by decode/spec ticks over the
    # wall time spent inside those ticks — the honest numerator/denominator
    # for a speculative-vs-baseline speedup (prefill is identical in both)
    decode_tokens: int = 0
    decode_wall_s: float = 0.0

    @property
    def busy_slot_fraction(self) -> float:
        return self.busy_slot_ticks / max(self.ticks * self.n_slots, 1)

    @property
    def tokens_per_s(self) -> float:
        return self.tokens_out / self.wall_s if self.wall_s > 0 else 0.0

    @property
    def accept_rate(self) -> float:
        return (self.spec_accepted / self.spec_proposed
                if self.spec_proposed > 0 else 0.0)

    @property
    def decode_tokens_per_s(self) -> float:
        return (self.decode_tokens / self.decode_wall_s
                if self.decode_wall_s > 0 else 0.0)

    def summary(self) -> Dict[str, Any]:
        return {
            "n_finished": self.n_finished,
            "n_dropped": self.n_dropped,
            "n_rejected": self.n_rejected,
            "ticks": self.ticks,
            "decode_ticks": self.decode_ticks,
            "prefill_ticks": self.prefill_ticks,
            "tokens_out": self.tokens_out,
            "wall_s": self.wall_s,
            "tokens_per_s": self.tokens_per_s,
            "busy_slot_fraction": self.busy_slot_fraction,
            "latency_s": _pct_dict(self.latencies_s),
            "ttft_s": _pct_dict(self.ttfts_s),
            "max_intertoken_gap_s": self.max_intertoken_gap_s,
            "self_heal": {
                "failed_ticks": self.failed_ticks,
                "n_crash_failures": self.n_crash_failures,
                "n_hang_failures": self.n_hang_failures,
                "n_recoveries": self.n_recoveries,
                "requeued_requests": self.requeued_requests,
                "straggler_ticks": self.straggler_ticks,
                "recovered_rows": self.recovered_rows,
            },
            "overload": {
                "n_preempted": self.n_preempted,
                "n_tier_shed": self.n_tier_shed,
            },
            "spec": {
                "spec_ticks": self.spec_ticks,
                "proposed": self.spec_proposed,
                "accepted": self.spec_accepted,
                "accept_rate": self.accept_rate,
                "decode_tokens": self.decode_tokens,
                "decode_wall_s": self.decode_wall_s,
                "decode_tokens_per_s": self.decode_tokens_per_s,
            },
        }


# --------------------------------------------------------------------------- #
# Program-backed step functions
# --------------------------------------------------------------------------- #

class _TPFirstPolicy(BackendPolicy):
    """Delegating wrapper used when serving on a mesh: the attention ops
    take their ``tp`` (shard_map-over-heads) backend whenever it is
    supported — i.e. the mesh's "model" axis divides both head counts —
    and every other decision goes to the wrapped policy.  GQA-small
    models simply never satisfy ``tp``'s supports() and fall through to
    the replicated backends."""

    def __init__(self, base: BackendPolicy):
        self.base = base

    def choose(self, node, in_specs):
        from repro.core.registry import backends_for
        from repro.kernels.serving_ops import TP_ATTENTION_OPS
        if node.op in TP_ATTENTION_OPS and \
                "tp" in backends_for(node.op, in_specs, node.attrs):
            return "tp"
        return self.base.choose(node, in_specs)


class ProgramStepper:
    """Owns the two compiled Programs plus the cache arrays they thread.

    Step dispatch goes through :meth:`Program.bind` — the positional
    fast-call path — because at serving batch sizes the per-call Python
    overhead of the kwargs path is a measurable fraction of a decode tick
    (``serve_bench`` reports both).
    """

    paged = False

    def __init__(self, cfg: GraphLMConfig, params: Mapping[str, Any], *,
                 n_slots: int, chunk: int, cache_cap: int,
                 policy: Optional[BackendPolicy] = None,
                 quantize: Optional[str] = None,
                 calib_ranges: Optional[Mapping[str, Any]] = None,
                 spec_k: int = 0, draft_layers: Optional[int] = None,
                 mesh: Optional[Any] = None):
        self.cfg = cfg
        self.n_slots = n_slots
        self.chunk = chunk
        self.cache_cap = cache_cap
        self.mesh = mesh
        if mesh is not None:
            policy = _TPFirstPolicy(policy or FixedPolicy())
        with self._mesh_ctx():
            dec_g = build_decode_graph(cfg, params, batch=n_slots,
                                       cache_cap=cache_cap)
            pre_g = build_prefill_graph(cfg, params, batch=n_slots,
                                        chunk=chunk, cache_cap=cache_cap)
            self.decode_program = compile(dec_g, policy=policy,
                                          quantize=quantize,
                                          calib_ranges=calib_ranges,
                                          mesh=mesh)
            self.prefill_program = compile(pre_g, policy=policy,
                                           quantize=quantize,
                                           calib_ranges=calib_ranges,
                                           mesh=mesh)
            self.cache_names = [v for v in dec_g.outputs[1:]]  # new_cache_*
            cache_inputs = sorted(init_cache_inputs(cfg, 1, 1))
            self._cache_input_names = cache_inputs
            self._input_names = ("tokens", "start", "n_new", *cache_inputs)
            # caches are threaded call-to-call and never reused -> donate
            # them (aliased in place on backends that support it)
            self._dec = self.decode_program.bind(*self._input_names,
                                                 donate=cache_inputs)
            self._pre = self.prefill_program.bind(*self._input_names,
                                                  donate=cache_inputs)
            self.caches: Dict[str, Any] = self._place_caches(
                init_cache_inputs(cfg, n_slots, cache_cap))
            verify_g = None
            if spec_k > 0:
                verify_g = build_verify_graph(cfg, params, batch=n_slots,
                                              width=spec_k + 1,
                                              cache_cap=cache_cap)
            self._init_spec(params, policy=policy, quantize=quantize,
                            calib_ranges=calib_ranges, spec_k=spec_k,
                            draft_layers=draft_layers, verify_graph=verify_g)

    def _mesh_ctx(self):
        """serving-mesh context for compiles and Program calls (no-op when
        single-device): publishes the mesh to the ``tp`` backends' supports
        guards at compile time and their shard_map bodies at trace time."""
        if self.mesh is None:
            return nullcontext()
        from repro.kernels.serving_ops import serving_mesh
        return serving_mesh(self.mesh)

    def _place_caches(self, caches: Mapping[str, Any]) -> Dict[str, Any]:
        """Device cache arrays; on a mesh each is ``jax.device_put`` to the
        NamedSharding the decode Program's partition stamped for it, so
        pools/caches/sidecars start life sharded instead of being
        resharded on the first call."""
        if self.mesh is None:
            return {k: jnp.asarray(v) for k, v in caches.items()}
        specs = self.decode_program.partition["specs"]
        return {k: jax.device_put(
                    jnp.asarray(v),
                    jax.sharding.NamedSharding(self.mesh, specs[k]))
                for k, v in caches.items()}

    def _stage(self, tokens: np.ndarray, start: np.ndarray,
               n_new: np.ndarray) -> Tuple[np.ndarray, ...]:
        """Host work before a step call; returns the inputs it adds
        after ``n_new`` (none for the dense caches)."""
        return ()

    def _call(self, fn, tokens, start, n_new):
        """One step Program call, in three profiler spans: staging and
        launch (``stepper.stage``), the host waiting on the device
        (``stepper.wait``), and the logits copy to the host
        (``stepper.fetch``)."""
        with jax.profiler.TraceAnnotation("stepper.stage"):
            extra = self._stage(tokens, start, n_new)
            cache_args = [self.caches[n] for n in sorted(self.caches)]
            with self._mesh_ctx():
                outs = fn(jnp.asarray(tokens), jnp.asarray(start),
                          jnp.asarray(n_new),
                          *[jnp.asarray(e) for e in extra], *cache_args)
        with jax.profiler.TraceAnnotation("stepper.wait"):
            outs[0].block_until_ready()
        with jax.profiler.TraceAnnotation("stepper.fetch"):
            logits = np.asarray(outs[0])
        for name, arr in zip(self.cache_names, outs[1:]):
            self.caches[name.replace("new_", "")] = arr
        return logits

    def _init_spec(self, params: Mapping[str, Any], *,
                   policy: Optional[BackendPolicy],
                   quantize: Optional[str],
                   calib_ranges: Optional[Mapping[str, Any]],
                   spec_k: int, draft_layers: Optional[int],
                   verify_graph, verify_donate: bool = True,
                   verify_bind_names: Optional[Tuple[str, ...]] = None,
                   verify_spec_ranges: bool = False) -> None:
        """Compile the speculative-decoding Programs (shared by the dense
        and paged steppers; ``verify_graph`` is the flavor-specific
        batched-verify variant of the target model).

        The DRAFT model is early-exit self-speculative: the target's
        first ``draft_layers`` layers plus its embedding and head, so no
        second set of weights exists and — because layer value names
        match the target's lower layers — the one shared calibration
        covers it (:func:`~repro.models.graph_lm.expand_spec_ranges`
        maps the ranges onto the unrolled step-suffixed names).  Its
        caches are PRIVATE per-slot dense buffers sized
        ``cache_cap + spec_k + 1`` (a draft call writes up to spec_k+1
        rows past the committed length and is never rolled back — stale
        rows are simply overwritten by the next catch-up or draft call,
        and draft attention never reads past its kv length)."""
        self.spec_k = spec_k
        if spec_k == 0:
            return
        cfg = self.cfg
        dl = (draft_layers if draft_layers is not None
              else max(1, cfg.n_layers // 2))
        if not 1 <= dl <= cfg.n_layers:
            raise ValueError(f"draft_layers {dl} outside "
                             f"[1, {cfg.n_layers}]")
        self.draft_layers = dl
        draft_cfg = replace(cfg, n_layers=dl)
        self.draft_cap = self.cache_cap + spec_k + 1
        draft_ranges = (expand_spec_ranges(dict(calib_ranges), spec_k)
                        if calib_ranges is not None else None)
        draft_g = build_draft_graph(draft_cfg, dict(params),
                                    batch=self.n_slots,
                                    cache_cap=self.draft_cap, spec_k=spec_k)
        draft_pre_g = build_prefill_graph(draft_cfg, dict(params),
                                          batch=self.n_slots,
                                          chunk=self.chunk,
                                          cache_cap=self.draft_cap)
        self.draft_program = compile(draft_g, policy=policy,
                                     quantize=quantize,
                                     calib_ranges=draft_ranges)
        self.draft_prefill_program = compile(draft_pre_g, policy=policy,
                                             quantize=quantize,
                                             calib_ranges=calib_ranges)
        # the kv8 seq verify's value names are step-suffixed like the
        # draft's, so it needs the expanded calibration to see the same
        # static scales the decode Program uses
        self.verify_program = compile(
            verify_graph, policy=policy, quantize=quantize,
            calib_ranges=draft_ranges if verify_spec_ranges
            else calib_ranges)
        draft_cache_inputs = sorted(init_cache_inputs(draft_cfg, 1, 1))
        names = ("tokens", "start", "n_new", *draft_cache_inputs)
        self._draft = self.draft_program.bind(*names,
                                              donate=draft_cache_inputs)
        self._draft_pre = self.draft_prefill_program.bind(
            *names, donate=draft_cache_inputs)
        # the kv8 verify program only READS the pages (its cache inputs
        # are not threaded back out), so donating them would invalidate
        # live buffers — the commit program gets the donation instead
        self._ver = self.verify_program.bind(
            *(verify_bind_names if verify_bind_names is not None
              else self._input_names),
            donate=self._cache_input_names if verify_donate else ())
        self._draft_cache_names = [v for v in draft_g.outputs[spec_k:]]
        self.draft_caches: Dict[str, Any] = {
            k: jnp.asarray(v)
            for k, v in init_cache_inputs(draft_cfg, self.n_slots,
                                          self.draft_cap).items()}

    def relocate_slots(self, moves: Sequence[Tuple[int, int]]) -> None:
        """Copy per-slot cache rows ``src -> dst`` — dense page-level
        resume for a request re-admitted to a different slot than the
        one whose rows it committed.  One batched gather per cache
        array (axis 0 is the slot axis): every source is read before
        any destination is written, so a pair of swapped slots
        relocates correctly.  Only the main caches move; private draft
        caches are rebuilt by draft catch-up (resume resets
        ``draft_len`` to 0), the same path a cold admission takes."""
        if not moves:
            return
        src = jnp.asarray([m[0] for m in moves], jnp.int32)
        dst = jnp.asarray([m[1] for m in moves], jnp.int32)
        for name in list(self.caches):
            arr = self.caches[name]
            self.caches[name] = arr.at[dst].set(arr[src])

    def backend_summary(self) -> Dict[str, Dict[str, Dict[str, int]]]:
        """Per-phase, per-op backend assignment counts — what the policy
        actually chose for the serving hot path.  Shape:
        ``{"prefill"|"decode"[|"verify"|"draft"]: {op: {backend:
        node_count}}}``; rendered by ``serve_bench --json`` and
        ``repro.tools.report.backend_table``."""
        phases = [("prefill", self.prefill_program),
                  ("decode", self.decode_program)]
        if self.spec_k:
            phases += [("verify", self.verify_program),
                       ("draft", self.draft_program)]
        out: Dict[str, Dict[str, Dict[str, int]]] = {}
        for phase, prog in phases:
            per_op: Dict[str, Dict[str, int]] = {}
            assignment = prog.assignment
            for node in prog.graph.nodes:
                counts = per_op.setdefault(node.op, {})
                b = assignment[node.name]
                counts[b] = counts.get(b, 0) + 1
            out[phase] = per_op
        return out

    def prefill(self, tokens: np.ndarray, start: np.ndarray,
                n_new: np.ndarray) -> np.ndarray:
        """tokens (B, chunk) → logits (B, chunk, V); caches advance."""
        return self._call(self._pre, tokens, start, n_new)

    def decode(self, tokens: np.ndarray, start: np.ndarray,
               n_new: np.ndarray) -> np.ndarray:
        """tokens (B, 1) → logits (B, V); caches advance."""
        return self._call(self._dec, tokens, start, n_new)

    def verify(self, tokens: np.ndarray, start: np.ndarray,
               n_new: np.ndarray) -> np.ndarray:
        """tokens (B, spec_k+1) — committed next token + draft proposals —
        → per-position logits (B, spec_k+1, V); MAIN caches advance by
        ``n_new[b]`` rows (rejected rows are garbage past the committed
        length the engine rolls the bookkeeping back to)."""
        return self._call(self._ver, tokens, start, n_new)

    def _draft_cache_args(self) -> List[Any]:
        return [self.draft_caches[n] for n in sorted(self.draft_caches)]

    def draft_prefill(self, tokens: np.ndarray, start: np.ndarray,
                      n_new: np.ndarray) -> np.ndarray:
        """Advance the private draft caches over already-committed tokens
        (cold start, prefix-hit fast-forward and post-recovery resume are
        all just ``draft_len < length`` catch-up).  Logits are returned
        for symmetry but unused — drafting starts from the committed next
        token, not from these."""
        with self._mesh_ctx():
            outs = self._draft_pre(jnp.asarray(tokens), jnp.asarray(start),
                                   jnp.asarray(n_new),
                                   *self._draft_cache_args())
        for name, arr in zip(self._draft_cache_names, outs[1:]):
            self.draft_caches[name.replace("new_", "")] = arr
        return np.asarray(outs[0])

    def draft(self, tokens: np.ndarray, start: np.ndarray,
              n_new: np.ndarray) -> np.ndarray:
        """One unrolled draft call: tokens (B, 1) — the committed next
        token — → (B, spec_k) greedy proposals; draft caches advance
        spec_k+1 rows (the final row makes a full accept need no
        catch-up before the next draft)."""
        with self._mesh_ctx():
            outs = self._draft(jnp.asarray(tokens), jnp.asarray(start),
                               jnp.asarray(n_new), *self._draft_cache_args())
        k = self.spec_k
        for name, arr in zip(self._draft_cache_names, outs[k:]):
            self.draft_caches[name.replace("new_", "")] = arr
        return np.concatenate([np.asarray(o) for o in outs[:k]], axis=1)


class PagedProgramStepper(ProgramStepper):
    """Paged variant: the per-slot dense caches are replaced by one shared
    page pool per layer plus per-sequence block tables
    (:class:`repro.runtime.kv_cache.BlockPool` owns the host-side block
    bookkeeping; this class owns the device page arrays and the compiled
    paged Programs).

    The engine's view is unchanged — same ``prefill(tokens, start,
    n_new)`` / ``decode(...)`` signatures — because this class records the
    written rows with the pool itself (it sees the token values and
    ``n_new``), applies any pending copy-on-write page copies to the
    device arrays, and threads the freshly built block tables into the
    Program call.  What the engine gains on top is the admission
    interface: :meth:`try_admit` (claim cached prefix blocks + reserve
    worst-case growth; ``None`` = not enough blocks right now),
    :meth:`attach` and :meth:`release`.
    """

    paged = True

    def __init__(self, cfg: GraphLMConfig, params: Mapping[str, Any], *,
                 n_slots: int, chunk: int, page_size: int, n_blocks: int,
                 max_pages: int, kv_dtype: str = "float32",
                 policy: Optional[BackendPolicy] = None,
                 quantize: Optional[str] = None,
                 calib_ranges: Optional[Mapping[str, Any]] = None,
                 spec_k: int = 0, draft_layers: Optional[int] = None,
                 mesh: Optional[Any] = None):
        self.cfg = cfg
        self.n_slots = n_slots
        self.chunk = chunk
        self.page_size = page_size
        self.n_blocks = n_blocks
        self.max_pages = max_pages
        self.kv_dtype = kv_dtype
        self.cache_cap = max_pages * page_size   # per-sequence logical cap
        self.mesh = mesh
        if mesh is not None:
            policy = _TPFirstPolicy(policy or FixedPolicy())
        with self._mesh_ctx():
            self._paged_init(params, policy=policy, quantize=quantize,
                             calib_ranges=calib_ranges, spec_k=spec_k,
                             draft_layers=draft_layers)

    def _paged_init(self, params, *, policy, quantize, calib_ranges,
                    spec_k, draft_layers):
        cfg, n_slots, chunk = self.cfg, self.n_slots, self.chunk
        page_size, n_blocks = self.page_size, self.n_blocks
        max_pages, kv_dtype = self.max_pages, self.kv_dtype
        mesh = self.mesh
        dec_g = build_paged_decode_graph(cfg, params, batch=n_slots,
                                         n_blocks=n_blocks,
                                         page_size=page_size,
                                         max_pages=max_pages,
                                         kv_dtype=kv_dtype)
        pre_g = build_paged_prefill_graph(cfg, params, batch=n_slots,
                                          chunk=chunk, n_blocks=n_blocks,
                                          page_size=page_size,
                                          max_pages=max_pages,
                                          kv_dtype=kv_dtype)
        self.decode_program = compile(dec_g, policy=policy, quantize=quantize,
                                      calib_ranges=calib_ranges, mesh=mesh)
        self.prefill_program = compile(pre_g, policy=policy, quantize=quantize,
                                       calib_ranges=calib_ranges, mesh=mesh)
        self.cache_names = [v for v in dec_g.outputs[1:]]
        cache_inputs = sorted(init_paged_cache_inputs(cfg, 1, 1,
                                                      kv_dtype=kv_dtype))
        self._cache_input_names = cache_inputs
        self._input_names = ("tokens", "start", "n_new", "block_tables",
                             *cache_inputs)
        self._dec = self.decode_program.bind(*self._input_names,
                                             donate=cache_inputs)
        self._pre = self.prefill_program.bind(*self._input_names,
                                              donate=cache_inputs)
        self.caches: Dict[str, Any] = self._place_caches(
            init_paged_cache_inputs(cfg, n_blocks, page_size,
                                    kv_dtype=kv_dtype))
        self.pool = BlockPool(
            n_blocks, page_size, kv_dtype=kv_dtype,
            page_bytes=kv_page_bytes(cfg.n_layers, cfg.n_kv_heads,
                                     cfg.d_head, page_size, kv_dtype))
        self._slot_seq: Dict[int, int] = {}
        verify_g = None
        ver_bind: Optional[Tuple[str, ...]] = None
        w = spec_k + 1
        if spec_k > 0 and kv_dtype == "int8":
            # quantize-on-write makes int8 page bytes history-dependent,
            # so the kv8 verify is the decode step unrolled width times in
            # one Program (bit-identical logits to plain decode) rather
            # than the chunk-shaped batched verify the fp32 flavors use
            verify_g = build_paged_verify_seq_graph(
                cfg, params, batch=n_slots, width=w, n_blocks=n_blocks,
                page_size=page_size, max_pages=max_pages)
            ver_bind = ("start", "block_tables",
                        *[f"tokens.s{j}" for j in range(w)],
                        *[f"n_new.s{j}" for j in range(w)],
                        *cache_inputs)
        elif spec_k > 0:
            verify_g = build_paged_verify_graph(cfg, params, batch=n_slots,
                                                width=w,
                                                n_blocks=n_blocks,
                                                page_size=page_size,
                                                max_pages=max_pages,
                                                kv_dtype=kv_dtype)
        self._init_spec(params, policy=policy, quantize=quantize,
                        calib_ranges=calib_ranges, spec_k=spec_k,
                        draft_layers=draft_layers, verify_graph=verify_g,
                        verify_donate=kv_dtype != "int8",
                        verify_bind_names=ver_bind,
                        verify_spec_ranges=kv_dtype == "int8")
        if spec_k > 0 and kv_dtype == "int8":
            commit_g = build_spec_commit_graph(
                cfg, batch=n_slots, width=w, n_blocks=n_blocks,
                page_size=page_size, max_pages=max_pages)
            self.spec_commit_program = compile(commit_g, policy=policy)
            # j-major, i-minor: the exact order the seq verify graph
            # emits its per-stage fp32 rows in
            kv_names = [x for j in range(w) for i in range(cfg.n_layers)
                        for x in (f"k_new{i}.s{j}", f"v_new{i}.s{j}")]
            self._commit = self.spec_commit_program.bind(
                "start", "block_tables",
                *[f"n_new.s{j}" for j in range(w)], *kv_names,
                *cache_inputs, donate=cache_inputs)
            self._pending_kv: Optional[List[Any]] = None

    # ---------------------------- admission --------------------------- #
    def try_admit(self, prompt: np.ndarray,
                  max_new_tokens: int) -> Optional[Tuple[int, int]]:
        """Claim the request's cached prefix and reserve its worst-case
        block count.  Returns ``(sequence id, reused_tokens)`` or ``None``
        when the pool cannot currently cover it (leave it queued)."""
        return self.pool.admit([int(t) for t in prompt], max_new_tokens)

    def attach(self, slot: int, sid: int) -> None:
        self._slot_seq[slot] = sid

    def release(self, slot: int, *, register: bool = True) -> None:
        """Return the slot's blocks to the pool; a finished sequence
        (``register=True``) leaves its pages in the prefix index for
        future prompts to share."""
        self.pool.release(self._slot_seq.pop(slot), register=register)

    # ------------------------------ steps ----------------------------- #
    def _record_writes(self, tokens: np.ndarray, start: np.ndarray,
                       n_new: np.ndarray) -> None:
        """Mirror this step's row writes into the pool (allocating pages
        and triggering CoW), then apply the resulting page copies to the
        device arrays BEFORE the Program call overwrites the new rows."""
        for s in range(self.n_slots):
            n = int(n_new[s])
            if n == 0:
                continue
            sid = self._slot_seq[s]
            seq = self.pool.sequence(sid)
            assert seq.n_tokens == int(start[s]), \
                f"slot {s}: pool at {seq.n_tokens}, engine writing {start[s]}"
            self.pool.append(sid, [int(t) for t in tokens[s, :n]])
        copies = self.pool.take_copies()
        if copies:
            src = jnp.asarray([c[0] for c in copies], jnp.int32)
            dst = jnp.asarray([c[1] for c in copies], jnp.int32)
            # axis 0 is the block id for every cache array — the int8
            # page pools AND their (N, Hk) scale sidecars — so one gather/
            # scatter keeps a quantized CoW copy bit-identical to its source
            for name in list(self.caches):
                arr = self.caches[name]
                self.caches[name] = arr.at[dst].set(arr[src])

    def _tables(self) -> np.ndarray:
        bt = np.zeros((self.n_slots, self.max_pages), np.int32)
        for s, sid in self._slot_seq.items():
            table = self.pool.block_table(sid)
            bt[s, :len(table)] = table
        return bt

    def _stage(self, tokens: np.ndarray, start: np.ndarray,
               n_new: np.ndarray) -> Tuple[np.ndarray, ...]:
        self._record_writes(tokens, start, n_new)
        return (self._tables(),)

    def verify(self, tokens: np.ndarray, start: np.ndarray,
               n_new: np.ndarray) -> np.ndarray:
        """fp32 pages: speculative rows go through the normal paged write
        path and the engine calls :meth:`BlockPool.truncate` afterward to
        roll the rejected tail back (pages past the committed length are
        appended-to-only this tick, the same argument
        ``BlockPool.snapshot`` relies on for recovery — and fp32 page
        writes are exact, so rejected rows leave no residue).

        int8 pages: the verify program is the decode step unrolled
        ``n_new``-wide with its quantize-on-write page state threaded
        INTERNALLY and then discarded — each stage's logits are
        bit-identical to what plain decode would produce at that
        position, but the live pages are left untouched (a rejected
        row raising a page scale would lossily requantize its committed
        neighbours).  Pool bookkeeping + CoW still happen up front so
        the block tables cover the speculative rows; the per-stage fp32
        K/V rows come back and are stashed for :meth:`commit_spec` to
        replay after acceptance."""
        if self.kv_dtype == "int8":
            self._record_writes(tokens, start, n_new)
            w = self.spec_k + 1
            cols = [jnp.asarray(tokens[:, j:j + 1]) for j in range(w)]
            masks = [jnp.asarray((n_new > j).astype(np.int32))
                     for j in range(w)]
            cache_args = [self.caches[n] for n in sorted(self.caches)]
            outs = self._ver(jnp.asarray(start),
                             jnp.asarray(self._tables()),
                             *cols, *masks, *cache_args)
            self._pending_kv = list(outs[w:])
            return np.stack([np.asarray(o) for o in outs[:w]], axis=1)
        return self._call(self._ver, tokens, start, n_new)

    def commit_spec(self, start: np.ndarray, n_acc: np.ndarray) -> None:
        """kv8 only: replay the accepted prefix (``n_acc[b]`` rows) of the
        verify call's write sequence against the live pages.  The pool
        already covers these rows (recorded before the verify call, then
        :meth:`BlockPool.truncate`\\ d back to the accepted length), so
        there is no pool work here — just the write-chain Program.
        Replaying a write that already happened is bit-idempotent
        (identical rows quantize to identical bytes and never raise a
        page scale), which is what makes a crashed-and-retried or
        hang-discarded commit recoverable."""
        w = self.spec_k + 1
        masks = [jnp.asarray((n_acc > j).astype(np.int32))
                 for j in range(w)]
        cache_args = [self.caches[n] for n in sorted(self.caches)]
        outs = self._commit(jnp.asarray(start),
                            jnp.asarray(self._tables()),
                            *masks, *self._pending_kv, *cache_args)
        for name, arr in zip(self.cache_names, outs):
            self.caches[name.replace("new_", "")] = arr
        self._pending_kv = None


# --------------------------------------------------------------------------- #
# The engine
# --------------------------------------------------------------------------- #

@dataclass
class _SlotState:
    req: EngineRequest
    pos: int = 0          # stream tokens prefilled so far
    length: int = 0       # valid cache entries
    next_token: int = 0
    decoding: bool = False
    # committed rows present in the PRIVATE draft cache (speculative
    # engines only).  Starts at 0 — cold start, prefix-hit fast-forward
    # and post-recovery resume are all the same "draft_len < length"
    # catch-up, which is why recovery never has to roll draft caches back
    draft_len: int = 0
    # the token stream prefill walks: the request's prompt, or — for a
    # request requeued by recovery — prompt + tokens generated before the
    # failure (re-prefilling them rebuilds the cache rows; argmax at the
    # final position is the NEXT token, so nothing is re-emitted)
    stream: Optional[np.ndarray] = None

    @property
    def prompt(self) -> np.ndarray:
        return self.req.prompt if self.stream is None else self.stream


class TickFailure(RuntimeError):
    """A guarded tick crashed or overran the hang deadline.  With
    ``self_heal`` the engine recovers internally; this escapes only when
    recovery is disabled or ``max_recoveries`` consecutive failures give
    up (a deterministic crash loop is not something to retry forever)."""

    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


@dataclass
class CheckpointSlot:
    """In-flight state of one slot, sufficient to rebuild it: the original
    request identity, every token generated so far (the resume stream is
    ``prompt + out_tokens``), the number of committed KV rows the slot
    had written (``rows`` — what page-level resume fast-forwards past),
    and — paged — the sequence id and block table whose pages survive
    recovery."""

    slot: int
    uid: int
    prompt: np.ndarray
    out_tokens: List[int]
    rows: int = 0
    sid: Optional[int] = None
    block_table: List[int] = field(default_factory=list)

    @property
    def stream(self) -> np.ndarray:
        return np.concatenate(
            [np.asarray(self.prompt, np.int32),
             np.asarray(self.out_tokens, np.int32)])


@dataclass
class EngineCheckpoint:
    """Host-side engine state captured at the start of a guarded tick —
    everything recovery needs (queued requests stay in the scheduler and
    are only mutated between ticks, so they need no snapshot)."""

    tick: int
    slots: List[CheckpointSlot]
    pool: Optional[Dict[str, Any]] = None    # BlockPool.snapshot()


@dataclass
class _Resume:
    """Pending resume of a requeued in-flight request (keyed by uid).
    ``slot``/``rows`` drive dense page-level resume: the per-slot cache
    rows this request committed in ``slot`` are still valid unless an
    intervening admission overwrote them (``Engine._dense_rows`` tracks
    the current owner of every slot's rows)."""

    stream: np.ndarray
    sid: Optional[int] = None
    slot: Optional[int] = None
    rows: int = 0


class Engine:
    """Deterministic tick-based serving loop over a :class:`ProgramStepper`.

    Each :meth:`step` is one tick: expire deadlines, admit queued requests
    to free slots, then run either one prefill-chunk Program call or one
    decode Program call over the whole slot batch.  When both phases have
    work the engine alternates, which bounds any request's inter-token gap
    to roughly one chunk of someone else's prompt.
    """

    def __init__(self, stepper: ProgramStepper, *, eos_id: int = -1,
                 max_queue: Optional[int] = None,
                 self_heal: bool = False,
                 hang_timeout: Optional[float] = None,
                 max_recoveries: int = 8,
                 coordinator: Optional[Coordinator] = None,
                 host_id: str = "engine",
                 tier_aware: bool = False,
                 slo_ttft_ticks: Optional[int] = None):
        self.stepper = stepper
        self.n_slots = stepper.n_slots
        self.chunk = stepper.chunk
        self.cache_cap = stepper.cache_cap
        self.paged = stepper.paged
        self.spec_k = getattr(stepper, "spec_k", 0)
        self.eos_id = eos_id
        self.sched = SlotScheduler(self.n_slots, max_queue=max_queue)
        self.slots: List[Optional[_SlotState]] = [None] * self.n_slots
        self.tick = 0
        self.finished: List[EngineRequest] = []
        self.dropped: List[EngineRequest] = []
        self.metrics = EngineMetrics(n_slots=self.n_slots)
        self._last_was_prefill = False
        self._t0: Optional[float] = None
        # (head uid, pool version) of the last admission gate refusal —
        # skips re-running the prefix lookup every tick while nothing that
        # could free blocks has happened
        self._gate_blocked: Optional[Tuple[int, int]] = None
        # ---- tier-aware overload control ----
        self.tier_aware = tier_aware
        self.slo_ttft_ticks = slo_ttft_ticks
        # dense page-level resume: slot -> uid whose cache rows currently
        # occupy that slot (an admission overwrites them; resume checks
        # this before trusting surviving rows)
        self._dense_rows: Dict[int, int] = {}
        # ---- self-healing (ft/ watchdogs wired into the tick loop) ----
        self.self_heal = self_heal
        self.hang_timeout = hang_timeout
        self.max_recoveries = max_recoveries
        self._watchdog = StepWatchdog()
        self._hang = (HangDetector(hang_timeout, lambda: None)
                      if hang_timeout is not None else None)
        self._resume: Dict[int, _Resume] = {}      # uid -> pending resume
        self._consec_failures = 0
        self.coordinator = coordinator
        self.host_id = host_id
        if coordinator is not None:
            coordinator.register(host_id)

    # ------------------------------------------------------------------ #
    def submit(self, req: EngineRequest) -> bool:
        """Admission control: False (with ``req.dropped`` set) when the
        queue is full or the request could never fit the cache.

        The fit check uses the UNPADDED prompt length: the cache stores
        ``len(prompt) + max_new_tokens - 1`` rows at most (the final
        generated token is emitted, never written back), and prefill
        padding rows are masked out of the cache write — so a prompt of
        exactly ``cache_cap`` tokens with ``max_new_tokens == 1`` is
        admissible.  (It used to be rejected after rounding the prompt up
        to a whole number of chunks.)"""
        req.submit_tick = self.tick
        req.t_submit = time.perf_counter()
        if len(req.prompt) == 0 or req.max_new_tokens < 1:
            return self._reject(req, "empty")
        need = len(req.prompt) + req.max_new_tokens - 1
        if need > self.cache_cap:
            return self._reject(req, "too_long")
        if self.paged and not self.stepper.pool.fits_ever(
                len(req.prompt), req.max_new_tokens):
            return self._reject(req, "too_long")
        if (self.tier_aware and self.sched.max_queue is not None
                and self.sched.queue_len >= self.sched.max_queue):
            # tier-aware shedding: a full queue evicts its lowest-priority
            # member (strictly below the arrival's tier) instead of turning
            # the arrival away — overload degrades the low tiers first
            victim = self.sched.shed_lowest(getattr(req, "priority", 0))
            if victim is not None:
                victim.dropped = "shed_low_tier"
                self.metrics.n_rejected += 1
                self.metrics.n_tier_shed += 1
                res = self._resume.pop(victim.uid, None)
                if res is not None and res.sid is not None:
                    # a preempted request shed from the queue still owns
                    # its pool sequence; those blocks must come back
                    self.stepper.pool.release(res.sid, register=False)
                self._finalize(victim)
        if not self.sched.submit(req):
            req.dropped = "queue_full"
            self.metrics.n_rejected += 1
            self._finalize(req)
            return False
        return True

    def _reject(self, req: EngineRequest, reason: str) -> bool:
        req.dropped = reason
        self.sched.reject(req)
        self.metrics.n_rejected += 1
        self._finalize(req)
        return False

    def _finalize(self, req: EngineRequest) -> None:
        req.finish_tick = self.tick
        req.t_done = time.perf_counter()
        if req.on_finish is not None:
            req.on_finish(req)

    # ------------------------------------------------------------------ #
    def _emit(self, st: _SlotState, tok: int) -> None:
        req = st.req
        now = time.perf_counter()
        req.out_tokens.append(tok)
        self.metrics.tokens_out += 1
        if req.t_first is None:
            req.t_first = now
            req.first_token_tick = self.tick
            self.metrics.ttfts_s.append(req.ttft_s or 0.0)
        if req._t_last_token is not None:
            gap = now - req._t_last_token
            req.max_gap_s = max(req.max_gap_s, gap)
            self.metrics.max_intertoken_gap_s = max(
                self.metrics.max_intertoken_gap_s, gap)
        req._t_last_token = now
        if req._last_token_tick is not None:
            req.max_gap_ticks = max(req.max_gap_ticks,
                                    self.tick - req._last_token_tick)
        req._last_token_tick = self.tick
        if req.on_token is not None:
            req.on_token(req, tok)

    def _finish_slot(self, slot: int) -> None:
        st = self.slots[slot]
        req = self.sched.finish(slot)
        assert req is st.req
        req.done = True
        self.slots[slot] = None
        if self.paged:
            # finished sequences donate their pages to the prefix index
            self.stepper.release(slot, register=True)
        self.finished.append(req)
        self.metrics.n_finished += 1
        self._finalize(req)
        self.metrics.latencies_s.append(req.latency_s or 0.0)

    def _drop_slot(self, slot: int, reason: str) -> None:
        st = self.slots[slot]
        req = self.sched.drop(slot)
        assert req is st.req
        req.dropped = reason
        self.slots[slot] = None
        if self.paged:
            self.stepper.release(slot, register=False)
        self.dropped.append(req)
        self.metrics.n_dropped += 1
        self._finalize(req)

    def _expire(self) -> None:
        expired = self.sched.drop_queued(
            lambda r: r.deadline_tick is not None and self.tick >= r.deadline_tick)
        for req in expired:
            req.dropped = "deadline"
            # a requeued in-flight request still owns its pool sequence;
            # expiring in the queue must return those blocks
            res = self._resume.pop(req.uid, None)
            if res is not None and res.sid is not None:
                self.stepper.pool.release(res.sid, register=False)
            self.dropped.append(req)
            self.metrics.n_dropped += 1
            self._finalize(req)
        for slot, st in enumerate(self.slots):
            if st is not None and st.req.deadline_tick is not None \
                    and self.tick >= st.req.deadline_tick:
                self._drop_slot(slot, "deadline")

    # ------------------------------------------------------------------ #
    # tier-aware overload control
    # ------------------------------------------------------------------ #
    def _ttft_budget(self, req: EngineRequest) -> Optional[int]:
        """Absolute tick by which ``req`` must emit its first token: the
        tighter of the engine-wide TTFT SLO (relative to submit) and the
        request's own deadline.  ``None`` when neither applies."""
        budget = (None if self.slo_ttft_ticks is None
                  else req.submit_tick + self.slo_ttft_ticks)
        if req.deadline_tick is not None:
            budget = (req.deadline_tick if budget is None
                      else min(budget, req.deadline_tick))
        return budget

    def _overload_control(self) -> None:
        """Preempt a running low-tier slot when the highest-priority
        queued request would otherwise blow its TTFT budget.

        Deterministic trigger: every slot is busy, the queue head
        outranks the lowest-priority running request, and the head's
        remaining budget no longer covers its own chunked prefill (with
        decode interleaving, one chunk lands roughly every other tick)
        plus one tick of slack.  At most one slot is preempted per tick,
        bounding the disruption; the victim is the lowest-priority slot,
        ties broken toward the most remaining work (it would hold the
        slot longest).  The victim requeues at its original position and
        resumes via the page-level path — its pages stay live, so the
        preemption costs pool capacity, not recompute."""
        head = self.sched.peek()
        if head is None or any(s is None for s in self.slots):
            return
        budget = self._ttft_budget(head)
        if budget is None:
            return
        need = 2 * -(-len(head.prompt) // self.chunk) + 1
        if self.tick + need < budget:
            return
        pri = getattr(head, "priority", 0)
        victim: Optional[Tuple[Tuple[int, int], int]] = None
        for slot, st in enumerate(self.slots):
            p = getattr(st.req, "priority", 0)
            if p >= pri:
                continue
            remaining = st.req.max_new_tokens - len(st.req.out_tokens)
            key = (p, -remaining)
            if victim is None or key < victim[0]:
                victim = (key, slot)
        if victim is not None:
            self._preempt_slot(victim[1])

    def _preempt_slot(self, slot: int) -> None:
        """Move a running request back to the queue at its original
        submit position, keeping everything it computed: its pool
        sequence (paged — pages and reservations stay live) or its dense
        cache rows, plus ``prompt + out_tokens`` as the resume stream.
        Not a terminal state: busy -> queued keeps conservation, exactly
        like recovery's requeue."""
        st = self.slots[slot]
        req = self.sched.preempt(slot)
        assert req is st.req
        req.n_requeues += 1
        rows = st.length if st.decoding else st.pos
        stream = np.concatenate([np.asarray(req.prompt, np.int32),
                                 np.asarray(req.out_tokens, np.int32)])
        sid = self.stepper._slot_seq.pop(slot) if self.paged else None
        self._resume[req.uid] = _Resume(stream=stream, sid=sid,
                                        slot=slot, rows=rows)
        self.slots[slot] = None
        self.metrics.n_preempted += 1
        self._gate_blocked = None

    # ------------------------------------------------------------------ #
    def step(self) -> None:
        """One scheduling tick (see class docstring)."""
        if self._t0 is None:
            self._t0 = time.perf_counter()
        self.tick += 1
        self.metrics.ticks += 1
        with jax.profiler.TraceAnnotation("engine.step", tick=self.tick):
            with jax.profiler.TraceAnnotation("engine.schedule"):
                self._expire()
                if self.tier_aware:
                    self._overload_control()
                self._admit()
                prefill = [i for i, st in enumerate(self.slots)
                           if st is not None and not st.decoding]
                decode = [i for i, st in enumerate(self.slots)
                          if st is not None and st.decoding]
                ckpt = (self.checkpoint()
                        if self.self_heal and (prefill or decode) else None)
            try:
                if prefill and (not decode or not self._last_was_prefill):
                    self._prefill_tick(prefill)
                    self._last_was_prefill = True
                elif decode:
                    if self.spec_k:
                        self._spec_decode_tick(decode)
                    else:
                        self._decode_tick(decode)
                    self._last_was_prefill = False
                self._consec_failures = 0
                if self.coordinator is not None:
                    self.coordinator.heartbeat(self.host_id)
            except TickFailure as failure:
                if not self.self_heal:
                    raise
                self._recover(ckpt, failure)
        self.metrics.wall_s = time.perf_counter() - self._t0

    def _admit(self) -> None:
        """Move queued requests into free slots (and resume requeued
        ones from the rows they kept)."""
        if self.paged:
            # admission is gated on BLOCK availability, not slot count
            # alone.  The gate performs the pool admission (claims cached
            # prefix blocks + reserves worst-case growth) so consecutive
            # admissions in one tick see each other's reservations.
            pool = self.stepper.pool
            head = self.sched.peek()
            if head is None or self._gate_blocked != (head.uid, pool.version):
                claims: Dict[int, Tuple[int, int]] = {}
                refused: List[EngineRequest] = []

                def gate(req: EngineRequest) -> bool:
                    res = self._resume.get(req.uid)
                    if res is not None and res.sid is not None:
                        # requeued in-flight request: it kept its sequence
                        # (blocks + reservations) across recovery, so no
                        # pool admission is needed — or possible
                        return True
                    admitted = self.stepper.try_admit(req.prompt,
                                                      req.max_new_tokens)
                    if admitted is None:
                        refused.append(req)
                        return False
                    claims[id(req)] = admitted
                    return True

                for slot, req in self.sched.admit(gate):
                    res = self._resume.pop(req.uid, None)
                    if res is not None and res.sid is not None:
                        # resume from the surviving block table: prefill
                        # fast-forwards past every row already in the pool
                        self.stepper.attach(slot, res.sid)
                        done = self.stepper.pool.sequence(res.sid).n_tokens
                        self.slots[slot] = _SlotState(req=req, pos=done,
                                                      stream=res.stream)
                        self.metrics.recovered_rows += done
                        continue
                    sid, reused = claims[id(req)]
                    self.stepper.attach(slot, sid)
                    # a prefix hit fast-forwards prefill past the reused rows
                    self.slots[slot] = _SlotState(req=req, pos=reused)
                # remember a refused head: until a block reaches refcount 0
                # or a reservation returns (pool.version bump), re-running
                # its prefix lookup every tick cannot change the answer
                self._gate_blocked = ((refused[0].uid, pool.version)
                                      if refused else None)
        else:
            # dense page-level resume: committed per-slot cache rows
            # survive a discarded tick or a preemption (writes are
            # positional, and rows a failed tick wrote past the committed
            # length are overwritten before they are ever read), so a
            # resumed request fast-forwards past them — relocating the
            # rows when it lands in a different slot.  An intervening
            # admission overwrites a slot's rows; ``owners`` is checked
            # against the pre-tick map (nothing is written until the
            # prefill call later this tick), and a clobbered resume falls
            # back to the always-correct full re-prefill of the stream.
            owners = dict(self._dense_rows)
            moves: List[Tuple[int, int]] = []
            for slot, req in self.sched.admit():
                res = self._resume.pop(req.uid, None)
                if res is None:
                    self.slots[slot] = _SlotState(req=req)
                elif (res.rows > 0 and res.slot is not None
                        and owners.get(res.slot) == req.uid):
                    if res.slot != slot:
                        moves.append((res.slot, slot))
                    self.slots[slot] = _SlotState(req=req, pos=res.rows,
                                                  stream=res.stream)
                    self.metrics.recovered_rows += res.rows
                else:
                    self.slots[slot] = _SlotState(req=req, stream=res.stream)
                self._dense_rows[slot] = req.uid
            if moves:
                self.stepper.relocate_slots(moves)

    def _guarded_call(self, fn, *args) -> np.ndarray:
        """One stepper Program call under the ft/ watchdogs.

        With ``self_heal``, a raised exception becomes a
        :class:`TickFailure` ("crash"), and a call that returns after the
        :class:`~repro.ft.watchdog.HangDetector` deadline fired is treated
        as hung — its result is DISCARDED by raising before any slot state
        or emission is touched.  (A real hung device call never returns;
        in this single-process simulation "returns too late" is the
        observable equivalent, and either way the recovery path is
        identical: restore the pre-tick checkpoint and requeue.)  The
        :class:`~repro.ft.watchdog.StepWatchdog` rolling median flags
        straggler ticks into the metrics either way."""
        self._watchdog.start()
        try:
            if self.self_heal and self._hang is not None:
                with self._hang as hd:
                    out = fn(*args)
                if hd.fired:
                    raise TickFailure("hang")
            else:
                out = fn(*args)
        except TickFailure:
            raise
        except Exception as e:
            if self.self_heal:
                raise TickFailure(f"crash: {type(e).__name__}: {e}") from e
            raise
        finally:
            if self._watchdog.stop():
                self.metrics.straggler_ticks += 1
        return out

    def _prefill_tick(self, slots: List[int]) -> None:
        b, c = self.n_slots, self.chunk
        tokens = np.zeros((b, c), np.int32)
        start = np.zeros((b,), np.int32)
        n_new = np.zeros((b,), np.int32)
        for s in slots:
            st = self.slots[s]
            stream = st.prompt
            n = min(c, len(stream) - st.pos)
            tokens[s, :n] = stream[st.pos:st.pos + n]
            start[s] = st.pos
            n_new[s] = n
        logits = self._guarded_call(self.stepper.prefill, tokens, start, n_new)
        self.metrics.prefill_ticks += 1
        self.metrics.busy_slot_ticks += len(slots)
        with jax.profiler.TraceAnnotation("engine.emit"):
            for s in slots:
                st = self.slots[s]
                n = int(n_new[s])
                st.pos += n
                if st.pos >= len(st.prompt):
                    st.decoding = True
                    st.length = len(st.prompt)
                    first = int(np.argmax(logits[s, n - 1]))
                    st.next_token = first
                    self._emit(st, first)
                    self._maybe_finish(s, first)

    def _decode_tick(self, slots: List[int]) -> None:
        t_begin = time.perf_counter()
        b = self.n_slots
        tokens = np.zeros((b, 1), np.int32)
        start = np.zeros((b,), np.int32)
        n_new = np.zeros((b,), np.int32)
        for s in slots:
            st = self.slots[s]
            tokens[s, 0] = st.next_token
            start[s] = st.length
            n_new[s] = 1
        logits = self._guarded_call(self.stepper.decode, tokens, start, n_new)
        self.metrics.decode_ticks += 1
        self.metrics.busy_slot_ticks += len(slots)
        with jax.profiler.TraceAnnotation("engine.emit"):
            for s in slots:
                st = self.slots[s]
                st.length += 1
                tok = int(np.argmax(logits[s]))
                st.next_token = tok
                self._emit(st, tok)
                self._maybe_finish(s, tok)
        self.metrics.decode_tokens += len(slots)
        self.metrics.decode_wall_s += time.perf_counter() - t_begin

    def _draft_catch_up(self, slots: List[int]) -> None:
        """Bring every slot's private draft cache up to its committed
        length with batched draft-prefill chunks over the committed token
        stream (original prompt + all generated tokens — the resume
        stream plus post-resume emissions collapse to exactly that)."""
        b, c = self.n_slots, self.chunk
        while True:
            behind = [s for s in slots
                      if self.slots[s].draft_len < self.slots[s].length]
            if not behind:
                return
            tokens = np.zeros((b, c), np.int32)
            start = np.zeros((b,), np.int32)
            n_new = np.zeros((b,), np.int32)
            for s in behind:
                st = self.slots[s]
                full = np.concatenate(
                    [np.asarray(st.req.prompt, np.int32),
                     np.asarray(st.req.out_tokens, np.int32)])
                n = min(c, st.length - st.draft_len)
                tokens[s, :n] = full[st.draft_len:st.draft_len + n]
                start[s] = st.draft_len
                n_new[s] = n
            self._guarded_call(self.stepper.draft_prefill,
                               tokens, start, n_new)
            for s in behind:
                self.slots[s].draft_len += int(n_new[s])

    def _spec_decode_tick(self, slots: List[int]) -> None:
        """Speculative decode tick: one draft call proposes ``spec_k``
        greedy tokens per slot, one verify call scores all of them (plus
        the committed next token) against the target in a single
        prefill-shaped Program call, and the greedy acceptance walk emits
        every proposal that matches the target's own argmax — so the
        emitted stream is token-identical to plain decode, just produced
        in fewer Program calls.  Rejected speculative cache rows are
        rolled back with :meth:`BlockPool.truncate` (paged) or simply
        overwritten by the next write at the committed position (dense:
        ``cache_update`` writes are positional)."""
        t_begin = time.perf_counter()
        b, k = self.n_slots, self.spec_k
        width = k + 1
        self._draft_catch_up(slots)
        tokens = np.zeros((b, 1), np.int32)
        start = np.zeros((b,), np.int32)
        n_new = np.zeros((b,), np.int32)
        for s in slots:
            st = self.slots[s]
            tokens[s, 0] = st.next_token
            start[s] = st.length
            n_new[s] = 1
        draft_toks = self._guarded_call(self.stepper.draft,
                                        tokens, start, n_new)
        vtokens = np.zeros((b, width), np.int32)
        vstart = np.zeros((b,), np.int32)
        vn_new = np.zeros((b,), np.int32)
        for s in slots:
            st = self.slots[s]
            remaining = st.req.max_new_tokens - len(st.req.out_tokens)
            n = min(width, remaining)   # never write past the request cap
            vtokens[s, 0] = st.next_token
            vtokens[s, 1:n] = draft_toks[s, :n - 1]
            vstart[s] = st.length
            vn_new[s] = n
        logits = self._guarded_call(self.stepper.verify,
                                    vtokens, vstart, vn_new)
        self.metrics.decode_ticks += 1
        self.metrics.spec_ticks += 1
        self.metrics.busy_slot_ticks += len(slots)
        # greedy acceptance walk: position i's argmax is what plain decode
        # would emit after vtokens[:i+1]; keep walking while the next fed
        # draft token IS that argmax.  Walk every slot BEFORE touching any
        # state — the kv8 commit below is one batched (guarded) call.
        emits: Dict[int, List[int]] = {}
        with jax.profiler.TraceAnnotation("engine.emit"):
            for s in slots:
                st = self.slots[s]
                n = int(vn_new[s])
                emit: List[int] = []
                for i in range(n):
                    g = int(np.argmax(logits[s, i]))
                    emit.append(g)
                    if g == self.eos_id or \
                            len(st.req.out_tokens) + len(emit) \
                            >= st.req.max_new_tokens:
                        break
                    if i + 1 < n and int(vtokens[s, i + 1]) == g:
                        continue
                    break
                emits[s] = emit         # len >= 1: position 0 re-scores the
                #                         committed token, so it always emits
        if self.paged:
            # roll back the rejected speculative rows; rows
            # 0..length+e-1 hold exactly the committed stream
            for s in slots:
                sid = self.stepper._slot_seq[s]
                self.stepper.pool.truncate(
                    sid, self.slots[s].length + len(emits[s]))
        if self.paged and getattr(self.stepper, "kv_dtype", None) == "int8":
            # the kv8 verify left the live pages untouched; replay the
            # accepted prefix of its write chain now that the block
            # tables are truncated back to exactly those rows
            commit_n = np.zeros((b,), np.int32)
            for s in slots:
                commit_n[s] = len(emits[s])
            self._guarded_call(self.stepper.commit_spec, vstart, commit_n)
        emitted_total = 0
        with jax.profiler.TraceAnnotation("engine.emit"):
            for s in slots:
                st = self.slots[s]
                emit = emits[s]
                e = len(emit)
                n = int(vn_new[s])
                self.metrics.spec_proposed += n - 1
                self.metrics.spec_accepted += e - 1
                st.length += e
                st.draft_len = st.length   # accepted rows == draft-cache rows
                st.next_token = emit[-1]
                for tok in emit:
                    self._emit(st, tok)
                emitted_total += e
                self._maybe_finish(s, emit[-1])
        self.metrics.decode_tokens += emitted_total
        self.metrics.decode_wall_s += time.perf_counter() - t_begin

    def _maybe_finish(self, slot: int, tok: int) -> None:
        st = self.slots[slot]
        if tok == self.eos_id or len(st.req.out_tokens) >= st.req.max_new_tokens:
            self._finish_slot(slot)

    # ------------------------------------------------------------------ #
    # self-healing: checkpoint / recover
    # ------------------------------------------------------------------ #
    def checkpoint(self) -> EngineCheckpoint:
        """In-flight state as of now: per-slot prompt + generated tokens
        (+ sequence id and block table when paged) and a full
        :meth:`~repro.runtime.kv_cache.BlockPool.snapshot`.  Taken at the
        start of every guarded tick; host-side slot state is only mutated
        after a successful Program call, so the checkpoint stays valid
        through any failure of the tick it guards."""
        slots: List[CheckpointSlot] = []
        for slot, st in enumerate(self.slots):
            if st is None:
                continue
            entry = CheckpointSlot(slot=slot, uid=st.req.uid,
                                   prompt=st.req.prompt,
                                   out_tokens=list(st.req.out_tokens),
                                   rows=st.length if st.decoding else st.pos)
            if self.paged:
                sid = self.stepper._slot_seq[slot]
                entry.sid = sid
                entry.block_table = self.stepper.pool.block_table(sid)
            slots.append(entry)
        pool = self.stepper.pool.snapshot() if self.paged else None
        return EngineCheckpoint(tick=self.tick, slots=slots, pool=pool)

    def _recover(self, ckpt: EngineCheckpoint, failure: TickFailure) -> None:
        """Discard the failed tick and rebuild from ``ckpt``: restore the
        pool (bookkeeping back in lockstep with the device pages — the
        failed tick's recorded-but-unwritten rows and index entries
        vanish), preempt every slot back into the queue at its original
        position, and stage each request's resume stream.  The next ticks
        re-admit them FIFO; paged requests keep their sequence, so prefill
        fast-forwards past every surviving row."""
        self.metrics.failed_ticks += 1
        if failure.reason == "hang":
            self.metrics.n_hang_failures += 1
        else:
            self.metrics.n_crash_failures += 1
        self._consec_failures += 1
        if self._consec_failures > self.max_recoveries:
            raise TickFailure(
                f"giving up after {self._consec_failures} consecutive "
                f"tick failures (last: {failure.reason})") from failure
        if self.paged:
            self.stepper.pool.restore(ckpt.pool)   # ends in check_integrity
            self.stepper._slot_seq.clear()
        for entry in ckpt.slots:
            req = self.sched.preempt(entry.slot)
            assert req.uid == entry.uid, \
                f"slot {entry.slot}: checkpoint uid {entry.uid}, live {req.uid}"
            req.n_requeues += 1
            self._resume[req.uid] = _Resume(stream=entry.stream,
                                            sid=entry.sid,
                                            slot=entry.slot,
                                            rows=entry.rows)
            self.slots[entry.slot] = None
            self.metrics.requeued_requests += 1
        self._gate_blocked = None
        self._last_was_prefill = False
        self.metrics.n_recoveries += 1
        if self.coordinator is not None:
            # a hang past the membership deadline shows up as a death;
            # re-registering is the "restarted engine" membership event
            self.coordinator.sweep()
            self.coordinator.register(self.host_id)

    # ------------------------------------------------------------------ #
    def reset_metrics(self) -> None:
        """Zero the metrics window (e.g. after warmup) without touching
        scheduler state, slots or caches."""
        self.metrics = EngineMetrics(n_slots=self.n_slots)
        self._t0 = None

    def has_work(self) -> bool:
        return self.sched.has_work()

    def run(self, max_ticks: int = 100_000) -> List[EngineRequest]:
        """Drive until queue and slots drain; returns newly finished
        requests (handed out exactly once)."""
        while self.has_work() and self.tick < max_ticks:
            self.step()
        out, self.finished = self.finished, []
        return out


# --------------------------------------------------------------------------- #
# Async front-end
# --------------------------------------------------------------------------- #

_DONE = object()


class AsyncEngine:
    """Cooperative asyncio facade: per-token streaming via ``async for``.

    Single-threaded and deterministic — :meth:`run` interleaves engine
    ticks with consumer wakeups on the current event loop; no background
    threads.
    """

    def __init__(self, engine: Engine):
        self.engine = engine
        self._uid = 0

    async def generate(self, prompt: np.ndarray, max_new_tokens: int, *,
                       priority: int = 0, deadline_tick: Optional[int] = None):
        """Async iterator of generated token ids for one request."""
        q: asyncio.Queue = asyncio.Queue()
        self._uid += 1
        req = EngineRequest(
            uid=self._uid, prompt=np.asarray(prompt, np.int32),
            max_new_tokens=max_new_tokens, priority=priority,
            deadline_tick=deadline_tick,
            on_token=lambda r, t: q.put_nowait(t),
            on_finish=lambda r: q.put_nowait(_DONE))
        if not self.engine.submit(req):
            raise RuntimeError(f"request rejected: {req.dropped}")
        while True:
            tok = await q.get()
            if tok is _DONE:
                break
            yield tok
        if req.dropped is not None:
            # a mid-flight drop (deadline) must not look like completion —
            # the consumer has only a truncated stream
            raise RuntimeError(
                f"request {req.uid} dropped after "
                f"{len(req.out_tokens)} tokens: {req.dropped}")

    async def run(self, max_ticks: int = 100_000) -> None:
        """Drive the engine until drained, yielding to consumers between
        ticks."""
        while self.engine.has_work() and self.engine.tick < max_ticks:
            self.engine.step()
            await asyncio.sleep(0)


# --------------------------------------------------------------------------- #
# Unbatched reference + the serving factory
# --------------------------------------------------------------------------- #

class UnbatchedReference:
    """No-batching greedy loop over B=1 Programs compiled from the same
    graphs (and, for int8, the same calibration ranges) as the engine's —
    the token-exactness oracle and serve_bench's baseline.

    ``chunk=None`` prefills the whole prompt in one Program call
    (one-shot); an integer chunk reproduces the engine's chunked prefill.
    Programs are compiled lazily per distinct (chunk,) shape and cached.
    """

    def __init__(self, cfg: GraphLMConfig, params: Mapping[str, Any], *,
                 cache_cap: int, policy: Optional[BackendPolicy] = None,
                 quantize: Optional[str] = None,
                 calib_ranges: Optional[Mapping[str, Any]] = None):
        self.cfg = cfg
        self.params = dict(params)
        self.cache_cap = cache_cap
        self._policy = policy
        self._quantize = quantize
        self._ranges = calib_ranges
        self._decode: Optional[Tuple[Any, List[str]]] = None
        self._prefills: Dict[int, Tuple[Any, List[str]]] = {}

    def _compiled(self, graph) -> Tuple[Any, List[str]]:
        prog = compile(graph, policy=self._policy, quantize=self._quantize,
                       calib_ranges=self._ranges)
        cache_inputs = sorted(init_cache_inputs(self.cfg, 1, 1))
        names = ("tokens", "start", "n_new", *cache_inputs)
        return (prog.bind(*names, donate=cache_inputs),
                [v for v in graph.outputs[1:]])

    def _prefill_for(self, chunk: int) -> Tuple[Any, List[str]]:
        if chunk not in self._prefills:
            g = build_prefill_graph(self.cfg, self.params, batch=1,
                                    chunk=chunk, cache_cap=self.cache_cap)
            self._prefills[chunk] = self._compiled(g)
        return self._prefills[chunk]

    def _decode_fn(self) -> Tuple[Any, List[str]]:
        if self._decode is None:
            g = build_decode_graph(self.cfg, self.params, batch=1,
                                   cache_cap=self.cache_cap)
            self._decode = self._compiled(g)
        return self._decode

    def generate(self, prompt: np.ndarray, max_new_tokens: int, *,
                 chunk: Optional[int] = None, eos_id: int = -1,
                 record: Optional[List] = None,
                 logits_out: Optional[List[np.ndarray]] = None) -> List[int]:
        """Greedy tokens for ``prompt``.  ``record`` receives every Program
        call's inputs (calibration); ``logits_out`` receives, per generated
        token, the (V,) logits row it was chosen from."""
        prompt = np.asarray(prompt, np.int32)
        if len(prompt) == 0 or max_new_tokens < 1:
            raise ValueError("need a non-empty prompt and max_new_tokens >= 1")
        c = len(prompt) if chunk is None else chunk
        # unpadded admission, matching Engine.submit: at most
        # len(prompt) + max_new - 1 rows are ever written (chunk padding
        # rows are masked out of the cache write)
        if len(prompt) + max_new_tokens - 1 > self.cache_cap:
            raise ValueError(f"prompt {len(prompt)} + {max_new_tokens} new "
                             f"tokens exceeds cache cap {self.cache_cap}")
        pre, cache_outs = self._prefill_for(c)
        caches = {k: jnp.asarray(v) for k, v in
                  init_cache_inputs(self.cfg, 1, self.cache_cap).items()}

        def call(fn, outs, tokens, start, n_new, kind):
            if record is not None:
                # host copies of the caches, read before the call donates them
                record.append((kind, {
                    "tokens": tokens, "start": start, "n_new": n_new,
                    **{k: np.asarray(v) for k, v in caches.items()}}))
            res = fn(jnp.asarray(tokens), jnp.asarray(start),
                     jnp.asarray(n_new), *[caches[k] for k in sorted(caches)])
            for name, arr in zip(outs, res[1:]):
                caches[name.replace("new_", "")] = arr
            return np.asarray(res[0])

        pos = 0
        logits = None
        while pos < len(prompt):
            n = min(c, len(prompt) - pos)
            toks = np.zeros((1, c), np.int32)
            toks[0, :n] = prompt[pos:pos + n]
            logits = call(pre, cache_outs,
                          toks, np.asarray([pos], np.int32),
                          np.asarray([n], np.int32), "prefill")
            pos += n
        row = logits[0, n - 1]
        out = [int(np.argmax(row))]
        if logits_out is not None:
            logits_out.append(row.copy())
        dec, dec_outs = self._decode_fn()
        length = len(prompt)
        while out[-1] != eos_id and len(out) < max_new_tokens:
            logits = call(dec, dec_outs,
                          np.asarray([[out[-1]]], np.int32),
                          np.asarray([length], np.int32),
                          np.asarray([1], np.int32), "decode")
            length += 1
            out.append(int(np.argmax(logits[0])))
            if logits_out is not None:
                logits_out.append(logits[0].copy())
        return out


def _merge_ranges(*range_dicts: Mapping[str, Any]) -> Dict[str, Any]:
    """Union of calibration ranges over value names: min lo, max hi.

    ``channel_mean`` is taken from the first dict that has the value —
    exact averaging would need per-batch counts.  It only feeds
    quantize-time bias correction, which never fires for the bias-free
    graph-LM dense nodes; revisit if the builder grows fused biases."""
    from repro.core.quant import ValueRange
    out: Dict[str, Any] = {}
    for d in range_dicts:
        for name, vr in d.items():
            if name in out:
                prev = out[name]
                out[name] = ValueRange(min(prev[0], vr[0]), max(prev[1], vr[1]),
                                       getattr(prev, "channel_mean", None))
            else:
                out[name] = vr
    return out


def shared_calibration(cfg: GraphLMConfig, params: Mapping[str, Any], *,
                       chunk: int, cache_cap: int, seed: int = 0,
                       n_prompts: int = 3,
                       max_new_tokens: int = 4) -> Dict[str, Any]:
    """One calibration for every Program variant of this model.

    Records real serving traffic (a few fp32 reference generations) as
    input batches for the B=1 prefill and decode graphs, calibrates each,
    and merges the ranges by value name.  Because the graph builders use
    identical value names across batch/chunk variants, the result drives
    ``compile(..., quantize="int8", calib_ranges=...)`` for the engine's
    batched Programs and the unbatched reference alike — giving every
    variant the same static activation scales (the precondition for
    batched-vs-unbatched token-exactness under int8)."""
    from repro.core.quant import calibrate
    ref = UnbatchedReference(cfg, params, cache_cap=cache_cap)
    rng = np.random.default_rng(seed)
    record: List[Tuple[str, Dict[str, Any]]] = []
    for _ in range(n_prompts):
        plen = int(rng.integers(1, max(2, 2 * chunk)))
        prompt = rng.integers(0, cfg.vocab, size=plen).astype(np.int32)
        ref.generate(prompt, max_new_tokens, chunk=chunk, record=record)
    pre_batches = [inputs for kind, inputs in record if kind == "prefill"]
    dec_batches = [inputs for kind, inputs in record if kind == "decode"]
    g_pre = build_prefill_graph(cfg, params, batch=1, chunk=chunk,
                                cache_cap=cache_cap)
    g_dec = build_decode_graph(cfg, params, batch=1, cache_cap=cache_cap)
    return _merge_ranges(calibrate(g_pre, pre_batches),
                         calibrate(g_dec, dec_batches))


def build_lm_serving(cfg: Optional[GraphLMConfig] = None, *,
                     n_slots: int = 4, chunk: int = 8, cache_cap: int = 64,
                     quantize: Optional[str] = None,
                     policy: Optional[BackendPolicy] = None,
                     seed: int = 0, eos_id: int = -1,
                     max_queue: Optional[int] = None,
                     params: Optional[Mapping[str, Any]] = None,
                     paged: bool = False, page_size: int = 8,
                     n_blocks: Optional[int] = None,
                     max_pages: Optional[int] = None,
                     kv_dtype: str = "float32",
                     self_heal: bool = False,
                     hang_timeout: Optional[float] = None,
                     max_recoveries: int = 8,
                     coordinator: Optional[Coordinator] = None,
                     spec_k: int = 0,
                     draft_layers: Optional[int] = None,
                     mesh: Optional[Any] = None,
                     tp: Optional[int] = None,
                     tier_aware: bool = False,
                     slo_ttft_ticks: Optional[int] = None,
                     ) -> Tuple[Engine, UnbatchedReference]:
    """Compile the serving Programs for a graph LM and return the engine
    plus its unbatched reference (sharing weights and, under int8, the
    calibrated activation scales).

    ``paged=True`` swaps the dense per-slot caches for the paged KV cache
    (:class:`PagedProgramStepper`): ``cache_cap`` becomes the per-sequence
    logical capacity (rounded up to whole pages of ``page_size``) and
    ``n_blocks`` sizes the shared pool — defaulting to the same total
    memory as the dense layout (``n_slots * ceil(cache_cap / page_size)``
    pages).  ``kv_dtype="int8"`` (paged only) stores the pools in int8
    with per-(page, kv-head) scale sidecars and routes the hot path
    through the fused-dequant ``*_q`` ops; at equal pool BYTES that is
    ~4x the page count of fp32.  The reference stays dense fp32 either
    way: it is the paged engine's token-exactness oracle.

    ``spec_k > 0`` turns on greedy speculative decoding: every decode
    tick drafts ``spec_k`` tokens with an early-exit draft model (the
    target's first ``draft_layers`` layers, default ``n_layers // 2``)
    and verifies them in one batched call — output stays token-identical
    to plain decode; only the number of Program calls per emitted token
    changes.

    ``tier_aware=True`` turns on tier-aware overload control: a full
    queue sheds its lowest-priority member to admit a higher-priority
    arrival, and a running low-tier slot is preempted (resuming later via
    the page-level path) when the highest-priority queued request would
    otherwise miss its TTFT budget (``slo_ttft_ticks`` and/or its
    deadline).

    ``mesh`` (a ``jax.sharding.Mesh`` with a "model" axis) or ``tp`` (a
    tensor-parallel degree, turned into such a mesh over the first ``tp``
    local devices) serves the engine multi-device: Programs compile with
    ``compile(mesh=...)``, caches/pools/sidecars are ``device_put`` onto
    their stamped NamedShardings, and attention runs the shard_map ``tp``
    backends — token-identical to the single-device engine (heads are
    computed whole per device; the only collective is an exact output
    all-gather).  The reference stays single-device: it is the oracle."""
    cfg = cfg or GraphLMConfig()
    if kv_dtype != "float32" and not paged:
        raise ValueError("kv_dtype requires paged=True")
    if tp is not None:
        if mesh is not None:
            raise ValueError("pass mesh or tp, not both")
        from repro.launch.mesh import make_serving_mesh
        mesh = make_serving_mesh(tp)
    params = dict(params) if params is not None else init_lm_params(cfg, seed)
    ranges = None
    if quantize is not None:
        ranges = shared_calibration(cfg, params, chunk=chunk,
                                    cache_cap=cache_cap, seed=seed)
    if paged:
        mp = max_pages if max_pages is not None else -(-cache_cap // page_size)
        nb = n_blocks if n_blocks is not None else n_slots * mp
        stepper: ProgramStepper = PagedProgramStepper(
            cfg, params, n_slots=n_slots, chunk=chunk, page_size=page_size,
            n_blocks=nb, max_pages=mp, kv_dtype=kv_dtype, policy=policy,
            quantize=quantize, calib_ranges=ranges,
            spec_k=spec_k, draft_layers=draft_layers, mesh=mesh)
    else:
        stepper = ProgramStepper(cfg, params, n_slots=n_slots, chunk=chunk,
                                 cache_cap=cache_cap, policy=policy,
                                 quantize=quantize, calib_ranges=ranges,
                                 spec_k=spec_k, draft_layers=draft_layers,
                                 mesh=mesh)
    engine = Engine(stepper, eos_id=eos_id, max_queue=max_queue,
                    self_heal=self_heal, hang_timeout=hang_timeout,
                    max_recoveries=max_recoveries, coordinator=coordinator,
                    tier_aware=tier_aware, slo_ttft_ticks=slo_ttft_ticks)
    reference = UnbatchedReference(cfg, params,
                                   cache_cap=max(cache_cap,
                                                 stepper.cache_cap),
                                   policy=policy, quantize=quantize,
                                   calib_ranges=ranges)
    return engine, reference
