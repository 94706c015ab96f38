"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (the dry-run sets
``--xla_force_host_platform_device_count=512`` before any jax import; tests
and benches see the real single CPU device).
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_test_mesh", "make_serving_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (one v5e pod).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; the "pod" axis is
    data-parallel by default (DCN-friendly: only gradient all-reduces cross
    pods) and is the pipeline axis when PP is enabled."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_test_mesh(data: int = 2, model: int = 2):
    """Small mesh for subprocess multi-device tests."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def make_serving_mesh(tp: int = 1, *, devices=None):
    """1-D ("model",) mesh over the first ``tp`` local devices — the shape
    the tensor-parallel serving engine wants (``build_lm_serving(tp=...)``
    and the ``--tp`` launch knob)."""
    devs = list(devices) if devices is not None else jax.devices()
    if tp < 1 or tp > len(devs):
        raise ValueError(f"tp={tp} needs 1..{len(devs)} devices")
    return jax.make_mesh((tp,), ("model",), devices=devs[:tp],
                         axis_types=(jax.sharding.AxisType.Auto,))
