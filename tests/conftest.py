"""Test config. NOTE: no XLA_FLAGS manipulation here — tests run on the
real single CPU device; only launch/dryrun.py fakes 512 devices.
Multi-device sharding tests spawn subprocesses with their own flags
(:func:`run_sub` below).

Also home of the shared ENGINE VARIANT MATRIX: the serving engine ships
in five flavors (dense, paged-fp32, paged-int8, speculative, TP=2) and
every behavioral guarantee — token exactness, fault recovery, page-level
resume — must hold on all of them.  Suites that used to carry private
per-variant parametrize lists (fault injection, kv8 serving,
speculative) draw from :data:`ENGINE_VARIANTS` via
:func:`engine_variants` / :func:`make_engine` instead, so adding a
variant extends every suite at once.  The ``tp2`` variant needs more
than one device: it is driven through :func:`run_sub` subprocesses with
forced host devices, never built in-process."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

def run_sub(code: str, n_dev: int = 8, timeout: int = 560) -> str:
    """Run ``code`` in a fresh interpreter with ``n_dev`` forced host
    devices and the repo on PYTHONPATH; assert success, return stdout."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={n_dev}")
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    return res.stdout


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


# --------------------------------------------------------------------------- #
# shared engine-variant matrix
# --------------------------------------------------------------------------- #

# The tiny config every engine suite shares: big enough for GQA
# (n_heads != n_kv_heads) and multi-layer cache plumbing, small enough
# that a full burst runs in seconds.
TINY_LM = dict(vocab=61, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
               d_ff=64)

# build_lm_serving kwargs per variant.  "spec" layers speculative
# decoding on the dense engine; suites that want speculation on a cache
# variant compose it themselves (make_engine(variant, spec_k=3)).
ENGINE_VARIANTS = {
    "dense": {},
    "paged-fp32": {"paged": True, "page_size": 8},
    "paged-int8": {"paged": True, "page_size": 8, "kv_dtype": "int8"},
    "spec": {"spec_k": 3},
    "tp2": {"tp": 2},
}


def engine_variants(*names):
    """``pytest.param`` list over the shared matrix for
    ``@pytest.mark.parametrize("variant,engine_kw", engine_variants(...))``.
    No names selects every variant.  Tests that include ``tp2`` must
    dispatch through :func:`run_sub` (a TP engine cannot build in the
    single-device test process)."""
    out = []
    for name in names or tuple(ENGINE_VARIANTS):
        out.append(pytest.param(name, dict(ENGINE_VARIANTS[name]), id=name))
    return out


def make_engine(variant, **overrides):
    """(engine, unbatched_reference) for one matrix variant on the
    shared tiny model; ``overrides`` layer on top of the variant kwargs
    (self_heal, spec_k, tier_aware, ...)."""
    from repro.models.graph_lm import GraphLMConfig
    from repro.runtime.engine import build_lm_serving

    if variant == "tp2":
        raise ValueError("tp2 engines only build under run_sub (needs a "
                         "multi-device mesh)")
    kw = dict(ENGINE_VARIANTS[variant])
    kw.update(overrides)
    kw.setdefault("n_slots", 3)
    kw.setdefault("chunk", 4)
    kw.setdefault("cache_cap", 48)
    return build_lm_serving(GraphLMConfig(**TINY_LM), **kw)


@pytest.fixture(scope="session")
def fault_seed():
    """Base seed for randomized fault-injection tick indices.  CI's
    fault-matrix job rotates it per run (ORPHEUS_FAULT_SEED=$run_id) so
    the matrix walks fresh crash/hang timings over time; locally it
    defaults to 0 for reproducible `pytest -x`."""
    return int(os.environ.get("ORPHEUS_FAULT_SEED", "0"))
