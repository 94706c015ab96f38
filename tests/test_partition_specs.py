"""Serving partition rules — in-process unit tests (no devices needed).

The rule set behind ``compile(mesh=...)``'s ``partition`` pass is pure
name/shape → PartitionSpec logic, so it is tested here on the real
graphs with a fake mesh object (``_div`` and friends only read
``axis_names`` / ``shape``).  The end-to-end multi-device exactness bar
lives in ``test_sharded_serving.py``.
"""

import types

import pytest
from jax.sharding import PartitionSpec as P

import repro  # noqa: F401  (registers every op/backend)
from repro.core.program import compile
from repro.models.graph_lm import (GraphLMConfig, build_decode_graph,
                                   build_paged_decode_graph, init_lm_params,
                                   partition_roles)
from repro.sharding.specs import (cache_specs, check_mesh_compat,
                                  graph_partition_specs, mesh_axes,
                                  serving_value_role)


def fake_mesh(**axes):
    """Duck-typed mesh: the spec rules only read axis_names and shape."""
    return types.SimpleNamespace(axis_names=tuple(axes), shape=dict(axes))


MESH2 = fake_mesh(data=1, model=2)


def _leaf(shape):
    return types.SimpleNamespace(shape=tuple(shape))


# --------------------------------------------------------------------------- #
# cache_specs: paged pools and scale sidecars across GQA ratios
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("hk", [1, 2, 4])
def test_cache_specs_paged_pool_divides_or_replicates(hk):
    """(N_pages, page, Hk*D) pools + (N_pages, Hk) sidecars: the lane axis
    shards on "model" when whole kv heads divide it, replicates otherwise
    — never a crash, whatever the GQA ratio."""
    tree = {"l0": {"pages_k": _leaf((10, 4, hk * 8)),
                   "pages_v": _leaf((10, 4, hk * 8)),
                   "pages_k_scale": _leaf((10, hk)),
                   "pages_v_scale": _leaf((10, hk))}}
    cfg = types.SimpleNamespace(n_kv_heads=hk)
    specs = cache_specs(tree, cfg, MESH2, batch=3)
    want_axis = "model" if hk % 2 == 0 else None
    assert specs["l0"]["pages_k"] == P(None, None, want_axis)
    assert specs["l0"]["pages_v"] == P(None, None, want_axis)
    assert specs["l0"]["pages_k_scale"] == P(None, want_axis)
    assert specs["l0"]["pages_v_scale"] == P(None, want_axis)


def test_cache_specs_paged_pool_never_batch_sharded():
    """A pool's leading dim is the block-pool size, not batch — even when
    the two collide numerically it must not pick up a data-parallel
    shard (rows are block-addressed across every request)."""
    mesh = fake_mesh(data=2, model=2)
    tree = {"pages_k": _leaf((4, 4, 2 * 8))}   # N_pages == 2*dp on purpose
    specs = cache_specs(tree, types.SimpleNamespace(n_kv_heads=2), mesh,
                        batch=3)
    assert specs["pages_k"] == P(None, None, "model")


# --------------------------------------------------------------------------- #
# serving_value_role / partition_roles
# --------------------------------------------------------------------------- #

def test_serving_value_role_classification():
    assert serving_value_role("l0.wq", (32, 32)) == "col"
    assert serving_value_role("l1.wg", (32, 64)) == "col"
    assert serving_value_role("l0.wk", (32, 16)) == "kv_col"
    # row-parallel candidates stay replicated (token-identity rationale)
    for name in ("l0.wo", "l0.wd", "embed", "head_w", "l0.norm1",
                 "final_norm", "logits"):
        assert serving_value_role(name, (32, 32)) == "replicated", name
    for name in ("tokens", "start", "n_new", "block_tables"):
        assert serving_value_role(name, (3,)) == "replicated", name
    assert serving_value_role("cache_k0", (3, 16, 2, 8)) == "dense_cache"
    assert serving_value_role("cache_k0", (10, 4, 2 * 8),
                              paged=True) == "paged_pool"
    assert serving_value_role("cache_v1_scale", (10, 2),
                              paged=True) == "kv_scale"
    # outputs mirror their input through the new_ prefix
    assert serving_value_role("new_cache_k0", (3, 16, 2, 8)) == "dense_cache"


def test_partition_roles_covers_every_graph_value():
    cfg = GraphLMConfig(vocab=61, d_model=32, n_layers=2, n_heads=4,
                        n_kv_heads=2, d_ff=64)
    params = init_lm_params(cfg)
    g = build_paged_decode_graph(cfg, params, batch=2, n_blocks=8,
                                 page_size=4, max_pages=4, kv_dtype="int8")
    roles = partition_roles(g)
    for name in list(g.inputs) + list(g.outputs):
        assert name in roles, name
    assert roles["cache_k0"] == "paged_pool"
    assert roles["cache_k0_scale"] == "kv_scale"
    assert roles["new_cache_v1"] == "paged_pool"
    assert roles["block_tables"] == "replicated"
    assert roles["logits"] == "replicated"


# --------------------------------------------------------------------------- #
# graph_partition_specs + the partition compile stage
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("hk,want", [(1, None), (2, "model"), (4, "model")])
def test_graph_specs_gqa_fallback(hk, want):
    cfg = GraphLMConfig(vocab=61, d_model=32, n_layers=1, n_heads=4,
                        n_kv_heads=hk, d_ff=64)
    params = init_lm_params(cfg)
    g = build_decode_graph(cfg, params, batch=2, cache_cap=16)
    specs = graph_partition_specs(g, MESH2)
    assert specs["cache_k0"] == (P(None, None, "model", None) if want
                                 else P())
    assert specs["new_cache_k0"] == specs["cache_k0"]
    # q heads always divide here; kv projections follow the kv-head count
    assert specs["l0.wq"] == P(None, "model")
    assert specs["l0.wk"] == (P(None, "model") if want else P())
    assert specs["l0.wo"] == P()
    assert specs["tokens"] == P()
    assert specs["logits"] == P()
    # the fp32 paged pool (N, P, Hk*D) has no head axis of its own: the
    # kv-head count comes from the rows its write takes
    g = build_paged_decode_graph(cfg, params, batch=2, n_blocks=8,
                                 page_size=4, max_pages=4)
    specs = graph_partition_specs(g, MESH2)
    assert specs["cache_k0"] == (P(None, None, "model") if want else P())
    assert specs["new_cache_v0"] == specs["cache_k0"]
    assert specs["l0.wk"] == (P(None, "model") if want else P())


def test_compile_mesh_stamps_frozen_partition():
    cfg = GraphLMConfig(vocab=61, d_model=32, n_layers=1, n_heads=4,
                        n_kv_heads=2, d_ff=64)
    params = init_lm_params(cfg)
    g = build_paged_decode_graph(cfg, params, batch=2, n_blocks=8,
                                 page_size=4, max_pages=4, kv_dtype="int8")
    prog = compile(g, mesh=MESH2)
    part = prog.partition
    assert part is not None
    assert dict(part["mesh"]) == {"data": 1, "model": 2}
    assert part["specs"]["cache_k0"] == P(None, None, "model")
    assert part["specs"]["cache_k0_scale"] == P(None, "model")
    # frozen: the mappings reject mutation
    with pytest.raises(TypeError):
        part["specs"]["cache_k0"] = P()
    # every value the engine exchanges has a spec
    for name in list(g.inputs) + list(g.outputs):
        assert name in part["specs"], name
    # the pass showed up in compile stats
    assert any(s.name == "partition" for s in prog.pass_stats)


def test_unpartitioned_compile_has_no_partition():
    cfg = GraphLMConfig(vocab=61, d_model=32, n_layers=1, n_heads=4,
                        n_kv_heads=2, d_ff=64)
    g = build_decode_graph(cfg, init_lm_params(cfg), batch=2, cache_cap=16)
    assert compile(g).partition is None


# --------------------------------------------------------------------------- #
# mesh identity / compatibility
# --------------------------------------------------------------------------- #

def test_check_mesh_compat():
    rec = mesh_axes(MESH2)
    check_mesh_compat(rec, fake_mesh(data=1, model=2))     # order-free match
    with pytest.raises(ValueError, match="mesh axes"):
        check_mesh_compat(rec, fake_mesh(data=1, model=4))
    with pytest.raises(ValueError, match="re-partition"):
        check_mesh_compat(rec, fake_mesh(model=2))
