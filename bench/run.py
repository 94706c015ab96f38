#!/usr/bin/env python3
"""One run of one benchmark cell on the machine it is started on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Everything it
uses is found by name: its configuration in ``bench/configs/<config>.json``
(with the model family's plain reference in ``bench/models/<family>.py``),
its traffic mix in ``bench/traffic/<traffic>.json``, the limits of its
correctness check in ``bench/checks/<workload>.json``, each per-layer
metric's reader in ``bench/metrics/<metric>.py`` and the chip's peaks in
``bench/peaks.json``.

A run makes the weights on the device from ``--seed``, builds the serving
engine of ``src/repro`` as deployed, warms its two step shapes, serves the
mix's preroll, and then measures for ``--seconds`` seconds.  Set-up
(``setup_s``) is everything from the start of the process to the start of
that window.  With ``--trace 1`` the window is traced by the profiler and
the per-layer metrics are printed instead of the end-to-end ones.  After
the window the engine is freed and what it served is compared with the
plain reference.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each compared number with its limit).
With no accelerator, or fewer chips than the cell asks for, it exits 2
and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from contextlib import nullcontext  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Any, Dict, List, Optional, Sequence  # noqa: E402

import numpy as np  # noqa: E402

# libtpu logs under /tmp/tpu_logs unless told otherwise; a run writes
# nothing outside its checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(BENCH, ".trace")

sys.path.insert(0, BENCH)
import traffic as traffic_mod  # noqa: E402
import trace_reduce  # noqa: E402


class BenchError(RuntimeError):
    """A run that cannot give a result: it exits non-zero, prints none."""


# --------------------------------------------------------------------------- #
# finding things by name
# --------------------------------------------------------------------------- #

class Layout:
    """Where a cell's files are found.  ``roots`` are searched in order,
    each holding ``configs/``, ``traffic/``, ``checks/``, ``metrics/`` and
    ``models/``; a test puts its own directory first."""

    def __init__(self, benchmark: str = os.path.join(ROOT, "BENCHMARK.json"),
                 roots: Sequence[str] = (BENCH,)):
        with open(benchmark) as f:
            self.spec = json.load(f)
        self.roots = list(roots)

    def path(self, kind: str, name: str, ext: str) -> str:
        for root in self.roots:
            p = os.path.join(root, kind, name + ext)
            if os.path.exists(p):
                return p
        raise BenchError(f"no {kind}/{name}{ext} under {self.roots}")

    def json(self, kind: str, name: str) -> Dict[str, Any]:
        with open(self.path(kind, name, ".json")) as f:
            return json.load(f)

    def module(self, kind: str, name: str):
        p = self.path(kind, name, ".py")
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", p)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def cell(self, workload: str) -> "Cell":
        cells = {w["name"]: w for w in self.spec["workloads"]}
        if workload not in cells:
            raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
        w = cells[workload]

        def mine(m):
            return workload in m.get("workloads", [workload])

        cfg = self.json("configs", w["config"])
        return Cell(
            name=workload, chips=int(w["chips"]), cfg=cfg,
            family=self.module("models", cfg["family"]),
            mix=self.json("traffic", w["traffic"]),
            checks=self.json("checks", workload),
            end_to_end=[m for m in self.spec["end_to_end"] if mine(m)],
            per_layer=[m for m in self.spec["per_layer"] if mine(m)],
            readers={m["name"]: self.module("metrics", m["name"])
                     for m in self.spec["per_layer"] if mine(m)})


@dataclass
class Cell:
    name: str
    chips: int
    cfg: Dict[str, Any]
    family: Any
    mix: Dict[str, Any]
    checks: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    readers: Dict[str, Any]

    @property
    def serving(self) -> Dict[str, int]:
        return self.cfg["serving"]

    @property
    def cache_cap(self) -> int:
        """Longest prompt plus longest output, in whole pages."""
        page = int(self.serving["page_size"])
        return -(-traffic_mod.max_context(self.mix) // page) * page


def peaks_for(kind: str, path: str = os.path.join(BENCH, "peaks.json")
              ) -> Dict[str, Any]:
    with open(path) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in {path}")
    return table[kind]


# --------------------------------------------------------------------------- #
# set-up
# --------------------------------------------------------------------------- #

def require_chips(chips: int):
    """The devices of this machine, or BenchError where there is no
    accelerator or fewer than ``chips``."""
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise BenchError("JAX finds no accelerator: this benchmark measures "
                         "the chip and does not fall back to the CPU")
    if len(devices) < chips:
        raise BenchError(f"the cell asks for {chips} chips, JAX finds "
                         f"{len(devices)}")
    return devices


def enable_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    so that every run of a cell after the first finds its programs."""
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class CompileCounter:
    """Counts the traces and backend compiles JAX reports."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in self.EVENTS:
            self.count += 1


def seed_key(seed: int):
    """A JAX key from any whole number (the seed may exceed 32 bits)."""
    import jax
    word = np.random.SeedSequence([seed, 0x3E16]).generate_state(1)[0]
    return jax.random.key(int(word) & 0x7FFFFFFF)


@dataclass
class Session:
    cell: Cell
    seed: int
    params: Dict[str, Any]
    engine: Any


def build(cell: Cell, seed: int) -> Session:
    """Weights on the device from ``seed``, the engine as deployed, and
    its two step shapes warmed."""
    import jax
    from repro.models.graph_lm import GraphLMConfig
    from repro.runtime.engine import build_lm_serving
    params = cell.family.init_params(cell.cfg, seed_key(seed))
    jax.block_until_ready(params)
    sv = cell.serving
    engine, _ = build_lm_serving(
        GraphLMConfig(**cell.family.serving_config(cell.cfg)),
        params=params, paged=True, page_size=int(sv["page_size"]),
        n_slots=int(sv["n_slots"]), chunk=int(sv["chunk"]),
        cache_cap=cell.cache_cap, n_blocks=int(sv["n_blocks"]), eos_id=-1)
    warm(engine.stepper)
    return Session(cell, seed, params, engine)


def warm(stepper) -> None:
    """One prefill and one decode call with every slot idle: the shapes
    the window uses are compiled, and no cache row is written."""
    b, c = stepper.n_slots, stepper.chunk
    zeros = np.zeros((b,), np.int32)
    stepper.prefill(np.zeros((b, c), np.int32), zeros, zeros)
    stepper.decode(np.zeros((b, 1), np.int32), zeros, zeros)


# --------------------------------------------------------------------------- #
# the load
# --------------------------------------------------------------------------- #

@dataclass
class Call:
    """One step Program call, as the benchmark's wrapper saw it."""
    kind: str                 # "prefill" | "decode"
    t0: float
    t1: float
    rows: np.ndarray          # per live slot: cached rows before the call
    n_new: np.ndarray         # per live slot: rows this call adds


class Recorder:
    """Wraps the stepper's ``prefill`` and ``decode`` (and, when traced,
    marks them with profiler spans): the host time of every call and
    which slots it served."""

    def __init__(self, stepper, *, traced: bool):
        self.calls: List[Call] = []
        self.traced = traced
        self.last = None          # (kind, logits, n_new) of the last call
        for kind in ("prefill", "decode"):
            base = f"_bench_base_{kind}"
            if not hasattr(stepper, base):
                setattr(stepper, base, getattr(stepper, kind))
            setattr(stepper, kind, self._wrap(kind, getattr(stepper, base)))

    def span(self, name: str):
        if not self.traced:
            return nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def _wrap(self, kind, fn):
        def call(tokens, start, n_new):
            live = np.flatnonzero(n_new)
            t0 = time.perf_counter()
            with self.span(f"bench.{kind}"):
                out = fn(tokens, start, n_new)
            self.calls.append(Call(kind, t0, time.perf_counter(),
                                   np.asarray(start)[live].copy(),
                                   np.asarray(n_new)[live].copy()))
            self.last = (kind, out, n_new)
            return out
        return call

    def logit_of(self, slots, req, tok: int) -> float:
        """The last call's own logit of the token it produced for ``req``:
        the row of the request's slot (``slots`` is the engine's list of
        slot states), at its last new row in a prefill."""
        kind, out, n_new = self.last
        slot = next(i for i, st in enumerate(slots)
                    if st is not None and st.req is req)
        row = out[slot] if kind == "decode" else out[slot, n_new[slot] - 1]
        return float(row[tok])


@dataclass
class Served:
    """One request as the benchmark saw it."""
    uid: int
    due: float                       # perf_counter time it was due
    prompt: np.ndarray
    max_new: int
    req: Any = None                  # the EngineRequest
    times: List[float] = field(default_factory=list)   # token emissions
    logits: List[float] = field(default_factory=list)  # each token's logit
    rejected: bool = False


class Driver:
    """Feeds a mix to the engine from one thread: an open loop submits
    each request once it is due, a backlog keeps the queue topped up."""

    def __init__(self, session: Session, mix: Dict[str, Any], *,
                 seed: int, seconds: float, traced: bool):
        self.engine = session.engine
        self.traffic = traffic_mod.Traffic(
            mix, seed=seed, vocab=int(session.cell.family.sizes(
                session.cell.cfg)["V"]),
            seconds=seconds, n_slots=self.engine.n_slots)
        self.recorder = Recorder(self.engine.stepper, traced=traced)
        self.served: List[Served] = []
        self.t_load = None
        if self.traffic.kind == "poisson":
            self._pending = self.traffic.scheduled()
        else:
            self._stream = self.traffic.stream()
        self._next = 0

    def _submit(self, r, due: float) -> None:
        from repro.runtime.engine import EngineRequest
        s = Served(r.uid, due, r.prompt, r.max_new)

        # the callback holds no reference to the engine, so that a
        # finished window frees it
        def on_token(req, tok, s=s, rec=self.recorder,
                     slots=self.engine.slots):
            s.times.append(time.perf_counter())
            s.logits.append(rec.logit_of(slots, req, tok))

        s.req = EngineRequest(uid=r.uid, prompt=r.prompt,
                              max_new_tokens=r.max_new, on_token=on_token)
        s.rejected = not self.engine.submit(s.req)
        self.served.append(s)

    def run_until(self, t_end: float) -> None:
        if self.t_load is None:
            self.t_load = time.perf_counter()
        eng = self.engine
        while True:
            now = time.perf_counter()
            if now >= t_end:
                return
            if self.traffic.kind == "poisson":
                while (self._next < len(self._pending) and
                       self.t_load + self._pending[self._next].due_s <= now):
                    r = self._pending[self._next]
                    self._submit(r, self.t_load + r.due_s)
                    self._next += 1
            else:
                while eng.sched.queue_len < self.traffic.queued:
                    self._submit(next(self._stream), now)
            if eng.has_work():
                with self.recorder.span("bench.step"):
                    eng.step()
            else:
                nxt = (self.t_load + self._pending[self._next].due_s
                       if self.traffic.kind == "poisson"
                       and self._next < len(self._pending) else t_end)
                time.sleep(max(0.0, min(nxt, t_end) - now))


@dataclass
class Window:
    t0: float
    t1: float
    served: List[Served]
    calls: List[Call]
    compiles: int
    trace: Optional[dict]
    setup_s: float


def serve(session: Session, *, seconds: float, traced: bool,
          counter: Optional[CompileCounter] = None,
          mix: Optional[Dict[str, Any]] = None) -> Window:
    """The preroll, then the measured window of ``seconds``."""
    import jax
    mix = mix or session.cell.mix
    drv = Driver(session, mix, seed=session.seed, seconds=seconds,
                 traced=traced)
    drv.run_until(time.perf_counter() + float(mix.get("preroll_s", 0.0)))
    if traced:
        import shutil
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
    n0 = counter.count if counter else 0
    t0 = time.perf_counter()
    setup_s = t0 - T_START
    with drv.recorder.span("bench.window"):
        drv.run_until(t0 + seconds)
    t1 = t0 + seconds
    trace = None
    if traced:
        jax.profiler.stop_trace()
        trace = trace_reduce.load(TRACE_DIR)
    return Window(t0, t1, drv.served, drv.recorder.calls,
                  (counter.count - n0) if counter else 0, trace, setup_s)


# --------------------------------------------------------------------------- #
# end-to-end metrics
# --------------------------------------------------------------------------- #

def quantile(xs: Sequence[float], q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else None


def ttfts(win: Window) -> List[float]:
    """Time to first token of every request due in the window, from its
    due time; one still waiting at the close counts its wait so far."""
    out = []
    for s in win.served:
        if win.t0 <= s.due < win.t1:
            first = s.times[0] if s.times else None
            out.append((first if first is not None and first <= win.t1
                        else win.t1) - s.due)
    return out


def gaps(win: Window) -> List[float]:
    """Every gap between consecutive tokens of one request whose later
    token came in the window."""
    out = []
    for s in win.served:
        t = np.asarray(s.times)
        if len(t) > 1:
            g = np.diff(t)
            out.extend(g[(t[1:] >= win.t0) & (t[1:] <= win.t1)].tolist())
    return out


def tokens_in_window(win: Window) -> int:
    return sum(int(np.sum((np.asarray(s.times) >= win.t0)
                          & (np.asarray(s.times) <= win.t1)))
               for s in win.served)


def end_to_end(cell: Cell, win: Window) -> Dict[str, Optional[float]]:
    seconds = win.t1 - win.t0
    values = {"setup_s": win.setup_s,
              "ttft_p90_s": quantile(ttfts(win), 90),
              "itl_p95_s": quantile(gaps(win), 95),
              "output_tokens_per_s": tokens_in_window(win) / seconds}
    return {m["name"]: values.get(m["name"]) for m in cell.end_to_end}


def attempted_failed(win: Window) -> tuple:
    """Requests sent up to the close, and those the engine turned away or
    dropped."""
    sent = [s for s in win.served if s.due < win.t1]
    failed = [s for s in sent if s.rejected or s.req.dropped is not None]
    return len(sent), len(failed)


# --------------------------------------------------------------------------- #
# correctness
# --------------------------------------------------------------------------- #

def sample(served: Sequence[Served], seed: int, tokens: int) -> List[Served]:
    """Finished requests drawn from ``seed``: the longest one first, then
    others until they hold at least ``tokens`` served tokens."""
    done = [s for s in served if s.req is not None and s.req.done]
    if not done:
        return []
    longest = max(done, key=lambda s: (len(s.req.out_tokens), -s.uid))
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0xC4EC]))
    rest = [done[i] for i in rng.permutation(len(done)) if done[i] is not longest]
    out, n = [longest], len(longest.req.out_tokens)
    for s in rest:
        if n >= tokens:
            break
        out.append(s)
        n += len(s.req.out_tokens)
    return out


def best_logits(cell: Cell, params, picked: Sequence[Served], *,
                dtype=None) -> List[np.ndarray]:
    """For each request, at each position where a token was served, the
    best logit of the plain reference over the prompt and the served
    tokens.  ``dtype`` passes to the family's ``forward``; the default is
    the configuration's own arithmetic."""
    import jax
    import jax.numpy as jnp
    fam, cfg = cell.family, cell.cfg
    T = cell.cache_cap
    best = jax.jit(lambda logits: jnp.max(logits, axis=-1)
                   .astype(jnp.float32))
    kw = {} if dtype is None else {"dtype": dtype}
    out = []
    for s in picked:
        seq = np.concatenate([s.prompt, np.asarray(s.req.out_tokens,
                                                   np.int32)])
        row = np.zeros(T, np.int32)
        row[:len(seq) - 1] = seq[:-1]
        p, n = len(s.prompt), len(s.req.out_tokens)
        logits = fam.forward(cfg, params, jnp.asarray(row), **kw)
        out.append(np.asarray(best(logits))[p - 1:p - 1 + n]
                   .astype(np.float64))
        del logits
    return out


def served_logits(picked: Sequence[Served]) -> List[np.ndarray]:
    """The program's own logit of each token it served."""
    for s in picked:
        if len(s.logits) != len(s.req.out_tokens):
            raise BenchError(f"request {s.uid}: {len(s.req.out_tokens)} "
                             f"tokens served, {len(s.logits)} seen")
    return [np.asarray(s.logits, np.float64) for s in picked]


def check(cell: Cell, session_params, win: Window, seed: int, *,
          control: bool = False) -> Dict[str, Dict[str, float]]:
    """The numbers that decide ``correct``, each with its limit.

    ``served_logit_gap4`` is the mean, over every served position of a
    sample of finished requests, of the fourth power of the gap between
    the plain reference's best logit there and the program's own logit
    of the token it served.  It grows with the fourth power of the
    program's logit error (the mean square separates the bfloat16
    control from sound runs by too little), and a token that is not the
    best (altered, or from a wrong cache row) reads its whole gap.  With
    ``control`` the reference computed one precision step lower takes
    the program's place: its best logit at the same positions stands
    for the served one."""
    spec = cell.checks
    picked = sample(win.served, seed, int(spec["sample_tokens"]))
    short = sum(1 for s in win.served
                if s.req is not None and s.req.done
                and len(s.req.out_tokens) != s.max_new)
    checks = {"finished_sampled": {"value": float(len(picked)),
                                   "limit": 1.0, "at_least": True},
              "wrong_length": {"value": float(short), "limit": 0.0}}
    if picked:
        import jax.numpy as jnp
        ref = np.concatenate(best_logits(cell, session_params, picked))
        got = np.concatenate(
            best_logits(cell, session_params, picked, dtype=jnp.bfloat16)
            if control else served_logits(picked))
        gap = ref - got
        checks["served_logit_gap4"] = {
            "value": float(np.mean(gap ** 4)),
            "limit": float(spec["served_logit_gap4"])}
        print(f"compared {len(gap)} served tokens of {len(picked)} "
              f"requests; widest gap {float(np.max(np.abs(gap)))!r}, "
              f"mean square {float(np.mean(gap ** 2))!r}", file=sys.stderr)
    return checks


def passed(checks: Dict[str, Dict[str, float]]) -> bool:
    ok = "served_logit_gap4" in checks
    for c in checks.values():
        if c.get("at_least"):
            ok &= c["value"] >= c["limit"]
        else:
            ok &= c["value"] <= c["limit"]
    return bool(ok)


def plain_checks(checks):
    return {k: {"value": v["value"], "limit": v["limit"]}
            for k, v in checks.items()}


# --------------------------------------------------------------------------- #
# per-layer metrics
# --------------------------------------------------------------------------- #

@dataclass
class RunRecord:
    """What a per-layer reader is given: the cell, the chip's peaks, the
    window's step calls and, in a traced run, the reduced trace."""
    cell: Cell
    peaks: Dict[str, Any]
    window: Window

    @property
    def trace(self):
        return self.window.trace

    def calls(self, kind: str) -> List[Call]:
        """Calls of ``kind`` made wholly inside the window."""
        w = self.window
        return [c for c in w.calls if c.kind == kind
                and c.t0 >= w.t0 and c.t1 <= w.t1]

    def traced_calls(self, kind: str) -> List[tuple]:
        """``(call, span)`` for the calls of ``kind`` whose profiler span
        lies wholly in the traced window.  The trace holds the spans of
        the last calls made, one per call and in order."""
        if self.trace is None:
            return []
        spans = sorted(self.trace["spans"].get(f"bench.{kind}", []))
        calls = [c for c in self.window.calls if c.kind == kind]
        if not spans or len(spans) > len(calls):
            return []
        keep = set(trace_reduce.inside(self.trace, f"bench.{kind}"))
        return [(c, s) for c, s in zip(calls[-len(spans):], spans)
                if s in keep]


def per_layer(cell: Cell, record: RunRecord) -> Dict[str, Any]:
    out = {}
    for m in cell.per_layer:
        value = cell.readers[m["name"]].read(record)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# --------------------------------------------------------------------------- #

def device_info(devices, cell_chips: int) -> Dict[str, Any]:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:cell_chips])
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


def run_cell(cell: Cell, *, seed: int, seconds: float, traced: bool,
             devices, peaks: Dict[str, Any],
             breaker: Optional[Any] = None) -> Dict[str, Any]:
    """Set-up, window and check of one run; returns the result line.
    ``breaker`` (tests only) is called with the built session, to plant a
    fault under the timed path."""
    counter = CompileCounter()
    session = build(cell, seed)
    if breaker is not None:
        breaker(session)
    win = serve(session, seconds=seconds, traced=traced, counter=counter)
    device = device_info(devices, cell.chips)
    print(f"set-up {win.setup_s!r} s; compiles inside the window: "
          f"{win.compiles}; weights held once: "
          f"{sum(v.nbytes for v in session.params.values())} bytes",
          file=sys.stderr)
    attempted, failed = attempted_failed(win)
    if traced:
        metrics = per_layer(cell, RunRecord(cell, peaks, win))
        if win.trace is not None:
            device["busy_s"] = trace_reduce.busy_s(win.trace)
            device["window_s"] = trace_reduce.window_s(win.trace)
    else:
        values = end_to_end(cell, win)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if values[m["name"]] is not None}
    params = session.params
    session.engine = None
    del session
    gc.collect()
    checks = check(cell, params, win, seed)
    result = {"correct": passed(checks), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if traced and win.trace is not None:
        result["breakdown"] = {
            "device_ops": [list(x) for x in trace_reduce.top_ops(win.trace)],
            "idle_gaps": [list(x) for x in trace_reduce.idle_gaps(win.trace)]}
    result["checks"] = plain_checks(checks)
    for k, v in checks.items():
        print(f"check {k}: {v['value']!r} "
              f"({'at least' if v.get('at_least') else 'at most'} "
              f"{v['limit']!r})", file=sys.stderr)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = Layout().cell(args.workload)
        devices = require_chips(cell.chips)
        peaks = peaks_for(devices[0].device_kind)
        if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
            raise BenchError(f"no system under test at {ROOT}/src/repro")
        sys.path.insert(0, os.path.join(ROOT, "src"))
        enable_cache()
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                      traced=bool(args.trace), devices=devices, peaks=peaks)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
