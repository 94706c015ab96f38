"""The serving path's Pallas kernels compile for a TPU v5e.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
block shapes off the (8, 128) tiling, SMEM or VMEM over budget.  These
tests compile each ``pallas`` backend the engine selects, at phi3-mini
widths (B=4, Hq=Hk=32, D=96, page 16, 32 pages per sequence, 128 blocks),
for a described ``v5e:2x2`` topology: the TPU compiler runs here, and no
chip is needed.  Nothing runs, so results are checked elsewhere.

The topology is described inside a module fixture (never at import), so
only the test worker that runs this file loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest

import repro  # noqa: F401  (registers every op/backend)
from repro.core.registry import get_impl

B, H, D, PAGE, MP, N_BLOCKS, S = 4, 32, 96, 16, 32, 128, 512
T = 64                       # the prefill chunk
D_MODEL, D_FF = 3072, 8192


@pytest.fixture(scope="module")
def topo():
    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"   # else the compiler logs to /tmp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    prev_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here: nothing to test
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", prev_cache)
        compilation_cache.reset_cache()
        if prev_log is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = prev_log


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _specs(op, page):
    """(shape, dtype) of every input of ``op`` at the widths above."""
    n_pages = S // page
    f32, i32, i8 = jnp.float32, jnp.int32, jnp.int8
    pool = (N_BLOCKS, page, H, D)
    table, scales = ((B, n_pages), i32), ((N_BLOCKS, H), f32)
    return {
        "paged_decode_attention": [((B, H, D), f32), (pool, f32),
                                   (pool, f32), table, ((B,), i32)],
        "paged_chunk_attention": [((B, T, H, D), f32), (pool, f32),
                                  (pool, f32), table, ((B,), i32)],
        "paged_decode_attention_q": [((B, H, D), f32), (pool, i8), scales,
                                     (pool, i8), scales, table, ((B,), i32)],
        "paged_chunk_attention_q": [((B, T, H, D), f32), (pool, i8), scales,
                                    (pool, i8), scales, table, ((B,), i32)],
        "decode_attention": [((B, H, D), f32), ((B, S, H, D), f32),
                             ((B, S, H, D), f32), ((B,), i32)],
        "chunk_attention": [((B, T, H, D), f32), ((B, S, H, D), f32),
                            ((B, S, H, D), f32), ((B,), i32)],
        "rmsnorm": [((B, T, D_MODEL), f32), ((D_MODEL,), f32)],
        "dense": [((B, T, D_MODEL), f32), ((D_MODEL, D_FF), f32)],
    }[op]


@pytest.mark.parametrize("op,page", [
    ("paged_decode_attention", PAGE),
    ("paged_chunk_attention", PAGE),
    ("paged_decode_attention_q", PAGE),
    ("paged_chunk_attention_q", PAGE),
    # the int8 page the chip smoke serves with: one whole (32, 128) tile
    ("paged_decode_attention_q", 32),
    ("paged_chunk_attention_q", 32),
    ("decode_attention", PAGE),
    ("chunk_attention", PAGE),
    ("rmsnorm", PAGE),
    ("dense", PAGE),
])
def test_pallas_backend_compiles_for_v5e(one_chip, op, page):
    from repro.core.ir import TensorSpec
    specs = _specs(op, page)
    impl = get_impl(op, "pallas")
    assert impl.supports([TensorSpec(s, jnp.dtype(d).name) for s, d in specs],
                         {}), f"{op}: pallas not admitted at these shapes"
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]
    compiled = jax.jit(
        lambda *a: impl(list(a), {"interpret": False})).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
