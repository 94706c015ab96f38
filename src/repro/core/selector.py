"""Backend selection policies — the "selected at runtime" half of the paper.

Three policies, in increasing sophistication:

* :class:`FixedPolicy` — a preference list (optionally per-op / per-node),
  first supported backend wins.  This is Orpheus's manual runtime switch.
* :class:`CostModelPolicy` — analytic roofline estimate per backend
  (impl cost model / backend throughput profile), argmin of estimated time.
  Used on the TPU target where wall-clock measurement is unavailable.
* :class:`AutotunePolicy` — measure every supported backend on the node's
  actual shapes (jitted, warmed, min-of-k) and pick the fastest; results are
  cached by (op, backend, shape-signature).  This reproduces the paper's
  core workflow: comparing layer implementations in a consistent
  environment, per layer and per workload.
"""

from __future__ import annotations

import hashlib
import json
import os
import platform
import tempfile
import time
import warnings
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ir import Node, TensorSpec
from repro.core.registry import Cost, backends_for, get_impl, get_op

__all__ = [
    "BackendPolicy",
    "FixedPolicy",
    "CostModelPolicy",
    "AutotunePolicy",
    "HardwareProfile",
    "TPU_V5E",
    "HOST_CPU",
    "hardware_fingerprint",
    "default_cache_path",
]


@dataclass(frozen=True)
class HardwareProfile:
    """Peak throughput profile used by the analytic selector, with a
    per-backend efficiency de-rating (fraction of peak each backend is
    expected to sustain)."""

    name: str
    peak_flops: float            # FLOP/s
    hbm_bw: float                # B/s
    backend_efficiency: Tuple[Tuple[str, float], ...] = (
        ("pallas", 0.8), ("pallas_split", 0.75), ("xla", 0.65),
        ("winograd", 0.65), ("ref", 0.35),
    )

    def efficiency(self, backend: str) -> float:
        for b, e in self.backend_efficiency:
            if b == backend:
                return e
        return 0.5

    def est_seconds(self, backend: str, cost: Cost) -> float:
        eff = self.efficiency(backend)
        return max(cost.flops / (self.peak_flops * eff),
                   cost.bytes / (self.hbm_bw * eff))


# TPU v5e single chip (the deployment target) and a nominal host CPU
# (the measurement platform in this container — same regime as the paper's
# single-core Cortex-A73 evaluation).
TPU_V5E = HardwareProfile("tpu-v5e", peak_flops=197e12, hbm_bw=819e9)
HOST_CPU = HardwareProfile("host-cpu", peak_flops=5e10, hbm_bw=2e10)


class BackendPolicy:
    """Base: always ``ref``."""

    def choose(self, node: Node, in_specs: Sequence[TensorSpec]) -> str:
        avail = backends_for(node.op, in_specs, node.attrs)
        if not avail:
            raise ValueError(f"no supported backend for {node.op} {in_specs}")
        return "ref" if "ref" in avail else avail[0]

    # per-node explicit override always wins
    def resolve(self, node: Node, in_specs: Sequence[TensorSpec]) -> str:
        if node.backend is not None:
            avail = backends_for(node.op, in_specs, node.attrs)
            if node.backend not in avail:
                raise ValueError(
                    f"node {node.name}: pinned backend {node.backend!r} not "
                    f"supported here (available: {avail})")
            return node.backend
        return self.choose(node, in_specs)


@dataclass
class FixedPolicy(BackendPolicy):
    """Preference-ordered selection. ``prefer`` is global; ``per_op`` and
    ``per_node`` override it for specific ops / node names."""

    prefer: Sequence[str] = ("xla", "ref")
    per_op: Dict[str, Sequence[str]] = field(default_factory=dict)
    per_node: Dict[str, Sequence[str]] = field(default_factory=dict)

    def choose(self, node: Node, in_specs: Sequence[TensorSpec]) -> str:
        avail = backends_for(node.op, in_specs, node.attrs)
        for pref in (self.per_node.get(node.name), self.per_op.get(node.op),
                     self.prefer):
            if not pref:
                continue
            for b in pref:
                if b in avail:
                    return b
        if avail:
            return avail[0]
        raise ValueError(f"no supported backend for {node.op}")


@dataclass
class CostModelPolicy(BackendPolicy):
    """Analytic argmin over supported backends (no execution needed — works
    for the TPU target in this CPU-only container)."""

    profile: HardwareProfile = TPU_V5E

    def choose(self, node: Node, in_specs: Sequence[TensorSpec]) -> str:
        avail = backends_for(node.op, in_specs, node.attrs)
        if not avail:
            raise ValueError(f"no supported backend for {node.op}")
        best, best_t = None, float("inf")
        for b in avail:
            cost = get_impl(node.op, b).cost(in_specs, node.attrs)
            t = self.profile.est_seconds(b, cost)
            if t < best_t:
                best, best_t = b, t
        return best  # type: ignore[return-value]

    def estimate(self, node: Node, in_specs: Sequence[TensorSpec]) -> Dict[str, float]:
        return {b: self.profile.est_seconds(
                    b, get_impl(node.op, b).cost(in_specs, node.attrs))
                for b in backends_for(node.op, in_specs, node.attrs)}


def _spec_sig(specs: Sequence[TensorSpec], attrs: Dict[str, Any]) -> Tuple:
    def freeze(x):
        if isinstance(x, dict):
            return tuple(sorted((k, freeze(v)) for k, v in x.items()))
        if isinstance(x, (list, tuple)):
            return tuple(freeze(v) for v in x)
        if isinstance(x, np.ndarray):
            return ("nd", x.shape, str(x.dtype))
        return x

    return (tuple((s.shape, s.dtype) for s in specs), freeze(attrs))


def _sig_key(op: str, specs: Sequence[TensorSpec], attrs: Dict[str, Any]) -> str:
    """Stable string key for (op, shapes, attrs) — JSON-dict friendly."""
    return json.dumps([op, _spec_sig(specs, attrs)], sort_keys=True, default=str)


def hardware_fingerprint() -> str:
    """Identifies the machine a measurement is valid on.  Timings cached
    under one fingerprint are never reused on different hardware."""
    try:
        dev = jax.devices()[0]
        dev_sig = f"{dev.platform}/{getattr(dev, 'device_kind', '?')}"
    except Exception:
        dev_sig = "none"
    raw = "|".join([platform.machine(), platform.system(), dev_sig,
                    str(os.cpu_count()), jax.__version__])
    return hashlib.sha1(raw.encode()).hexdigest()[:16]


def default_cache_path() -> str:
    """Where benchmarks/examples persist autotune results by default
    (override with ORPHEUS_AUTOTUNE_CACHE)."""
    env = os.environ.get("ORPHEUS_AUTOTUNE_CACHE")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "orpheus",
                        "autotune.json")


_CACHE_VERSION = 1


@dataclass
class AutotunePolicy(BackendPolicy):
    """Measure-and-pick (the paper's consistent-environment comparison).

    Each candidate impl is jitted on random inputs matching the node's
    specs, warmed once, then timed ``reps`` times; min is recorded.  The
    in-memory cache makes repeated compiles of the same network free; with
    ``cache_path`` set, measurements persist as JSON across processes
    (keyed by op/backend/shape-signature under a hardware fingerprint), so
    a second compile of the same model on the same machine performs zero
    re-measurements.
    """

    reps: int = 5
    candidates: Optional[Sequence[str]] = None  # None = all supported
    cache_path: Optional[str] = None
    _cache: Dict[str, str] = field(default_factory=dict)
    _timings: Dict[str, Dict[str, float]] = field(default_factory=dict)
    n_measured: int = 0   # signatures actually benchmarked by this instance
    n_loaded: int = 0     # signatures preloaded from the on-disk cache
    # (mtime, size) of the cache file after our last write + its content,
    # so repeated saves skip re-parsing a file nobody else touched
    _disk_state: Optional[Tuple[Tuple[float, int], Dict[str, Any]]] = None

    def __post_init__(self) -> None:
        if self.cache_path:
            self._load_cache()

    # -------------------------- persistence --------------------------- #
    def _load_cache(self) -> None:
        """Best-effort preload: any corrupted, truncated or wrong-shaped
        cache file degrades to in-memory tuning instead of crashing the
        compile (the file is rewritten cleanly on the next measurement)."""
        try:
            with open(self.cache_path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            return
        if not isinstance(data, dict) or data.get("version") != _CACHE_VERSION:
            return
        fps = data.get("fingerprints")
        entries = fps.get(hardware_fingerprint()) if isinstance(fps, dict) else None
        if not isinstance(entries, dict):
            return
        for key, times in entries.items():
            if key in self._timings or not isinstance(times, dict):
                continue
            try:
                self._timings[key] = {b: float(t) for b, t in times.items()}
            except (TypeError, ValueError):
                continue
            self.n_loaded += 1

    def _save_cache(self) -> None:
        """Best-effort persist: an unwritable cache location degrades to
        in-memory-only tuning instead of failing the compile."""
        path = self.cache_path
        # merge with whatever is on disk (other processes / fingerprints),
        # skipping the re-read when nobody else has written since our last
        # save — measure() saves once per new signature, so this keeps a
        # cold-cache compile from re-parsing the file N times
        data: Dict[str, Any] = {"version": _CACHE_VERSION, "fingerprints": {}}
        try:
            stamp = (os.path.getmtime(path), os.path.getsize(path))
        except OSError:
            stamp = None
        if self._disk_state is not None and stamp == self._disk_state[0]:
            data = self._disk_state[1]
        elif stamp is not None:
            try:
                with open(path) as f:
                    prev = json.load(f)
                if isinstance(prev, dict) and prev.get("version") == _CACHE_VERSION:
                    data = prev
            except (OSError, ValueError):
                pass
        fp = hardware_fingerprint()
        if not isinstance(data.get("fingerprints"), dict):
            data["fingerprints"] = {}
        if not isinstance(data["fingerprints"].get(fp), dict):
            data["fingerprints"][fp] = {}
        data["fingerprints"][fp].update(self._timings)
        tmp = None
        try:
            os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
            fd, tmp = tempfile.mkstemp(
                dir=os.path.dirname(os.path.abspath(path)), suffix=".tmp")
            with os.fdopen(fd, "w") as f:
                json.dump(data, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
            self._disk_state = ((os.path.getmtime(path), os.path.getsize(path)),
                                data)
        except OSError as e:
            if tmp is not None and os.path.exists(tmp):
                os.unlink(tmp)
            warnings.warn(f"autotune cache not persisted to {path!r}: {e}")

    def _random_inputs(self, specs: Sequence[TensorSpec]) -> List[jax.Array]:
        rng = np.random.default_rng(0)
        out = []
        for s in specs:
            if np.issubdtype(np.dtype(s.dtype), np.floating) or s.dtype == "bfloat16":
                arr = rng.standard_normal(s.shape, dtype=np.float32)
                out.append(jnp.asarray(arr, dtype=s.dtype))
            else:
                out.append(jnp.asarray(rng.integers(0, 2, s.shape), dtype=s.dtype))
        return out

    def measure(self, op: str, in_specs: Sequence[TensorSpec],
                attrs: Dict[str, Any]) -> Dict[str, float]:
        """Timings for every candidate backend of (op, shapes, attrs).

        Incremental against the (possibly preloaded) cache: only backends
        with no cached timing are benchmarked, so a cache written under a
        different ``candidates`` restriction is topped up rather than
        trusted blindly.  ``supports()`` (via ``backends_for``) is the only
        filter: a backend it admits that then fails to compile or run is a
        bug, and its exception propagates instead of silently dropping the
        backend out of the choice.  The returned dict is filtered to the
        current candidate set."""
        key = _sig_key(op, in_specs, attrs)
        avail = backends_for(op, in_specs, attrs)
        if self.candidates is not None:
            avail = [b for b in avail if b in self.candidates]
        # an ``inf`` in a cache file written before failures propagated
        # marks a backend that was never measured: measure it now
        times = {b: t for b, t in self._timings.get(key, {}).items()
                 if t != float("inf")}
        missing = [b for b in avail if b not in times]
        if missing:
            inputs = self._random_inputs(in_specs)
            for b in missing:
                fn = get_impl(op, b)
                jf = jax.jit(lambda args: fn(args, attrs))
                jax.block_until_ready(jf(inputs))      # compile + warm up
                best = float("inf")
                for _ in range(self.reps):
                    t0 = time.perf_counter()
                    jax.block_until_ready(jf(inputs))
                    best = min(best, time.perf_counter() - t0)
                times[b] = best
            self._timings[key] = times
            self.n_measured += 1
            if self.cache_path:
                self._save_cache()
        return {b: t for b, t in times.items() if b in avail}

    def choose(self, node: Node, in_specs: Sequence[TensorSpec]) -> str:
        key = _sig_key(node.op, in_specs, node.attrs)
        if key in self._cache:
            return self._cache[key]
        avail = backends_for(node.op, in_specs, node.attrs)
        if self.candidates is not None:
            avail = [b for b in avail if b in self.candidates]
        if len(avail) == 1:
            # Nothing to compare: measuring would burn warm-up + reps
            # iterations to "choose" among one option.  This also skips the
            # runnability probe a measurement used to provide — a sole
            # candidate that cannot execute on this platform now fails at
            # first Program call instead of at compile; with one candidate
            # there is no alternative either way.
            self._cache[key] = avail[0]
            return avail[0]
        times = self.measure(node.op, in_specs, node.attrs)
        if not times:
            raise ValueError(f"no runnable backend for {node.op}")
        best = min(times, key=times.get)  # type: ignore[arg-type]
        self._cache[key] = best
        return best
