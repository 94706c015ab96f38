"""The serving path compiles for a TPU v5e as it should.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
block shapes off the (8, 128) tiling, SMEM or VMEM over budget.  These
tests compile each ``pallas`` backend the engine selects, at phi3-mini
widths (B=4, Hq=Hk=32, D=96, page 16, 32 pages per sequence, 128 blocks),
and the engine's paged decode and prefill Programs at both benchmark
cells' widths, whose optimized HLO must touch the page pools in place,
for a described ``v5e:2x2`` topology: the TPU compiler runs here, and no
chip is needed.  Nothing runs, so results are checked elsewhere.

The topology is described inside a module fixture (never at import), so
only the test worker that runs this file loads the TPU library.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
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
    pool = (N_BLOCKS, page, H * D)
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


# --------------------------------------------------------------------------- #
# The engine's paged step Programs at the benchmark cells' widths (two of
# their layers each): each step touches a layer's K or V page pool only
# through the in-place scatter of the new rows and one gather of the slots'
# pages: no whole-pool copy, relayout or convert (a pool stored (N, P, Hk,
# D) with D off the 128-lane tiling is laid out block-minor and copied into
# the scatter's layout and back), no fill select over the gathered view
# (jnp.take's default mode), and each donated pool aliased to its own
# update (else XLA copies pools to keep both buffers alive).  An MHA
# decode step also reads the gathered view in f32: its per-head products
# are matrix-vector ones, which XLA computes in f32, and the op keeps that.
# --------------------------------------------------------------------------- #

from repro.core.program import compile as compile_graph  # noqa: E402
from repro.models.graph_lm import (GraphLMConfig,  # noqa: E402
                                   build_paged_decode_graph,
                                   build_paged_prefill_graph)

CELL_WIDTHS = {
    "phi3-mini": GraphLMConfig(vocab=32064, d_model=3072, n_layers=2,
                               n_heads=32, n_kv_heads=32, d_ff=8192),
    "stablelm-2-12b": GraphLMConfig(vocab=100352, d_model=5120, n_layers=2,
                                    n_heads=32, n_kv_heads=8, d_ff=13824),
}
SLOTS, MAX_PAGES, CHUNK = 16, 80, 128
# more pages than the slots' tables reach, so a pool-sized array and a
# gathered (SLOTS, MAX_PAGES * PAGE, Hk*D) view differ in size
N_POOL = 1600
_HLO_OP = re.compile(r"^\s*(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]\S* ([\w-]+)\(",
                     re.M)
_ALIAS = re.compile(r"\{(\d+)\}: \((\d+), \{\}, \w+-alias\)")


def _zero_params(cfg):
    """Weights of the graph's shapes; zeros the host never touches."""
    dm, hq, hk, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    e, f = cfg.n_experts, cfg.d_expert
    shapes = {"embed": (cfg.vocab, dm), "final_norm": (dm,),
              "head_w": (dm, cfg.vocab)}
    for i in range(cfg.n_layers):
        shapes.update({
            f"l{i}.norm1": (dm,), f"l{i}.wq": (dm, hq * dh),
            f"l{i}.wk": (dm, hk * dh), f"l{i}.wv": (dm, hk * dh),
            f"l{i}.wo": (hq * dh, dm), f"l{i}.norm2": (dm,)})
        shapes.update({
            f"l{i}.router": (dm, e), f"l{i}.we_gate": (e, dm, f),
            f"l{i}.we_up": (e, dm, f), f"l{i}.we_down": (e, f, dm)}
            if e else {
            f"l{i}.wg": (dm, cfg.d_ff), f"l{i}.wu": (dm, cfg.d_ff),
            f"l{i}.wd": (cfg.d_ff, dm)})
    return {k: np.zeros(s, np.float32) for k, s in shapes.items()}


def _bound_step(cfg, step, one_chip):
    """(graph, lowered step) of a paged step Program at the cells' slots,
    chunk and pages, bound as the engine binds it: pools donated, in
    sorted name order."""
    kw = dict(batch=SLOTS, n_blocks=N_POOL, page_size=PAGE,
              max_pages=MAX_PAGES)
    params = _zero_params(cfg)
    graph = (build_paged_decode_graph(cfg, params, **kw) if step == "decode"
             else build_paged_prefill_graph(cfg, params, chunk=CHUNK, **kw))
    prog = compile_graph(graph)
    pools = sorted(n for n in graph.inputs if n.startswith("cache_"))
    names = ("tokens", "start", "n_new", "block_tables", *pools)
    step_fn = prog.bind(*names, donate=pools)
    spec = {k: jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype),
                                    sharding=one_chip)
            for k, s in graph.inputs.items()}
    pspec = {k: jax.ShapeDtypeStruct(np.shape(v), jnp.float32,
                                     sharding=one_chip)
             for k, v in params.items()}
    return graph, step_fn.lower(pspec, *[spec[n] for n in names])


@pytest.mark.parametrize("cell", sorted(CELL_WIDTHS))
@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_paged_step_touches_pools_only_in_place(one_chip, cell, step):
    cfg = CELL_WIDTHS[cell]
    graph, lowered = _bound_step(cfg, step, one_chip)
    _check_pools(graph, lowered.compile().as_text(), cfg, cell, step)


def _check_pools(graph, hlo, cfg, cell, step):
    pools = sorted(n for n in graph.inputs if n.startswith("cache_"))
    pool = N_POOL * PAGE * cfg.n_kv_heads * cfg.d_head
    view = SLOTS * MAX_PAGES * PAGE * cfg.n_kv_heads * cfg.d_head
    typed = [(op, dt, int(np.prod([int(d) for d in dims.split(",") if d])))
             for dt, dims, op in _HLO_OP.findall(hlo)]
    ops = [(op, n) for op, _, n in typed]
    assert ("scatter", pool) in ops, "no in-place pool write found"
    whole = [op for op, n in ops
             if op in ("copy", "transpose", "convert") and n == pool]
    assert not whole, f"{step} {cell}: whole-pool {whole}"
    fills = [op for op, n in ops if op == "select" and n == view]
    assert not fills, f"{step} {cell}: select over the gathered view"
    if step == "decode" and cfg.n_heads == cfg.n_kv_heads:
        # one query row per kv head: the arithmetic of an f32 multiply
        # and reduce, so no bf16 copy of the view
        halved = [op for op, dt, n in typed if dt == "bf16" and n == view]
        assert not halved, f"{cell}: decode rounds the gathered view"
    # each pool is updated in its own buffer: every new_<pool> output
    # aliases a donated parameter of its own (a pool handed another pool's
    # buffer would need one of the whole-pool copies refused above)
    head = hlo.split("\n", 1)[0]
    alias = {int(o): int(p) for o, p in _ALIAS.findall(head)}
    assert sorted(alias) == sorted(graph.outputs.index(f"new_{n}")
                                   for n in pools)
    assert len(set(alias.values())) == len(pools)


# --------------------------------------------------------------------------- #
# The mixture-of-experts configuration at published widths (mellum2, four
# layers: three sliding, one full).  Its steps keep the pools' guarantees
# above.  Its routed experts run as grouped matmuls in prefill (three a
# layer) and, at a decode step's 16 rows, as each expert's matmul batched
# over the rows; either way there is no array the size of the experts'
# weights gathered once per (row, expert) pair and no copy of a layer's
# expert stack, and every matmul of the step, the experts' included,
# states the configuration's HIGHEST precision.  The dense configurations'
# steps state no precision they did not state before: only phi3's MHA
# decode attention (two einsums a layer) runs at HIGHEST, as its op
# chooses.
# --------------------------------------------------------------------------- #

MELLUM = GraphLMConfig(vocab=98304, d_model=2304, n_layers=4, n_heads=32,
                       n_kv_heads=4, head_dim=128,
                       windows=(1024, 1024, 1024, 0), n_experts=64, top_k=8,
                       d_expert=896, matmul_precision="highest")
_PRECISION = re.compile(r"precision(?:_config)? = \[([^\]]*)\]")


def _precisions(lowered):
    """The precision each matmul of a lowered step states, in order."""
    return ["highest" if "HIGHEST" in p.upper() else "default"
            for p in _PRECISION.findall(lowered.as_text())]


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_moe_step_groups_its_experts_at_highest(one_chip, step):
    cfg = MELLUM
    graph, lowered = _bound_step(cfg, step, one_chip)
    text = lowered.as_text()
    grouped = 3 * cfg.n_layers if step == "prefill" else 0
    assert len(re.findall(r'"chlo.ragged_dot"\(', text)) == grouped
    prec = _precisions(lowered)
    assert prec and set(prec) == {"highest"}, prec
    hlo = lowered.compile().as_text()
    _check_pools(graph, hlo, cfg, "mellum2", step)
    rows = SLOTS * (1 if step == "decode" else CHUNK)
    per_pair = rows * cfg.top_k * cfg.d_model * cfg.d_expert
    stack = cfg.n_experts * cfg.d_model * cfg.d_expert
    typed = [(op, int(np.prod([int(d) for d in dims.split(",") if d])))
             for _, dims, op in _HLO_OP.findall(hlo)]
    assert max(n for _, n in typed) < per_pair
    moved = [op for op, n in typed
             if op in ("copy", "transpose", "convert") and n == stack]
    assert not moved, f"{step}: expert stack {moved}"
    assert "operand_precision={highest,highest}" in hlo


@pytest.mark.parametrize("cell", sorted(CELL_WIDTHS))
@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_dense_steps_state_no_new_precision(one_chip, cell, step):
    cfg = CELL_WIDTHS[cell]
    _, lowered = _bound_step(cfg, step, one_chip)
    prec = _precisions(lowered)
    mha_decode = step == "decode" and cfg.n_heads == cfg.n_kv_heads
    assert prec.count("highest") == (2 * cfg.n_layers if mha_decode else 0)
    assert "ragged_dot" not in lowered.as_text()


# --------------------------------------------------------------------------- #
# The prefill step's head at the cells' widths (phi3, stablelm, mellum2):
# norm and head run on each slot's last new row only, so the step returns
# (SLOTS, V) logits and holds no float array of SLOTS x CHUNK x V elements
# (the per-position logits once copied to the host on every prefill call).
# The one-hot embedding's token-id compare is that size in s32 and pred.
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("cell", sorted(CELL_WIDTHS) + ["mellum2"])
def test_prefill_heads_last_rows_only(one_chip, cell):
    cfg = CELL_WIDTHS.get(cell, MELLUM)
    graph, lowered = _bound_step(cfg, "prefill", one_chip)
    logits = lowered.out_info[graph.outputs.index("logits")]
    assert tuple(logits.shape) == (SLOTS, cfg.vocab)
    hlo = lowered.compile().as_text()
    full = SLOTS * CHUNK * cfg.vocab
    shapes = re.findall(r"\b(f32|bf16|f16)\[([\d,]+)\]", hlo)
    wide = {f"{dt}[{dims}]" for dt, dims in shapes
            if np.prod([int(d) for d in dims.split(",")]) == full}
    assert not wide, f"{cell}: per-position logits {wide}"
