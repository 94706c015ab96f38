"""The prefill Program's logits: each slot's last new row only.

A prefill chunk's next token is read at row ``n_new[b] - 1`` of each
slot, so the graph takes that row (``last_new_row``) before the final
norm and the head, and returns (B, V) logits.  Here, on tiny
configurations (dense, paged fp32, paged int8, and a mixture-of-experts
model with windowed layers), that output is compared with the
per-position (B, T, V) logits of the same graph with the row gather
taken out, at ragged ``n_new`` that include an idle slot and a full
chunk; the engine's tokens with the unbatched reference's; and the
stepper's addressing of what it returns.
"""

import numpy as np
import pytest
from conftest import TINY_LM, make_engine

import repro  # noqa: F401  (registers every op/backend)
from repro.core.ir import Graph, TensorSpec
from repro.core.program import compile
from repro.core.registry import get_op
from repro.kernels.serving_ops import last_new_row
from repro.models.graph_lm import (GraphLMConfig, build_decode_graph,
                                   build_paged_prefill_graph,
                                   build_prefill_graph, init_cache_inputs,
                                   init_lm_params, init_paged_cache_inputs)
from repro.runtime.engine import EngineRequest, LastRowLogits

TINY = GraphLMConfig(**TINY_LM)
# sliding x3 + full, window 6, 8 experts top-2, HIGHEST matmuls
TINY_MOE = GraphLMConfig(vocab=61, d_model=32, n_layers=4, n_heads=4,
                         n_kv_heads=2, head_dim=12, windows=(6, 6, 6, 0),
                         n_experts=8, top_k=2, d_expert=16,
                         matmul_precision="highest")
B, T, PAGE, MAX_PAGES, N_BLOCKS = 4, 8, 4, 6, 32
# slot 0 idle, slot 2 a full chunk, the others ragged; slot 1 and 3 start
# past earlier (zero) rows
N_NEW = np.asarray([0, 3, T, 1], np.int32)
START = np.asarray([0, 5, 0, 12], np.int32)

VARIANTS = {
    "dense": (TINY, None),
    "paged-fp32": (TINY, "float32"),
    "paged-int8": (TINY, "int8"),
    "moe-window": (TINY_MOE, "float32"),
}


def _graph(cfg, kv_dtype):
    params = init_lm_params(cfg, seed=4)
    if kv_dtype is None:
        return build_prefill_graph(cfg, params, batch=B, chunk=T,
                                   cache_cap=MAX_PAGES * PAGE)
    return build_paged_prefill_graph(cfg, params, batch=B, chunk=T,
                                     n_blocks=N_BLOCKS, page_size=PAGE,
                                     max_pages=MAX_PAGES, kv_dtype=kv_dtype)


def _per_position(graph):
    """The same graph with the row gather taken out: norm and head on
    every row of the chunk, logits (B, T, V)."""
    g = graph.clone()
    (gather,) = [n for n in g.nodes if n.op == "last_new_row"]
    src = gather.inputs[0]
    nodes = []
    for n in g.nodes:
        if n is gather or n.name == "logits_flat":
            continue
        if n.name == "final_norm_n":
            n = n.clone(inputs=[src, *n.inputs[1:]])
        if n.name == "lm_head":
            n = n.clone(outputs=["logits"])
        nodes.append(n)
    out = Graph(name=g.name + "_per_position", inputs=g.inputs,
                outputs=g.outputs, nodes=nodes, params=g.params)
    out.validate()
    return out


def _feeds(cfg, kv_dtype, graph):
    rng = np.random.default_rng(7)
    feeds = {"tokens": rng.integers(0, cfg.vocab, (B, T)).astype(np.int32),
             "start": START, "n_new": N_NEW}
    if kv_dtype is None:
        feeds.update(init_cache_inputs(cfg, B, MAX_PAGES * PAGE))
    else:
        # each slot its own pages, in no particular order
        perm = rng.permutation(N_BLOCKS)[:B * MAX_PAGES]
        feeds["block_tables"] = perm.reshape(B, MAX_PAGES).astype(np.int32)
        feeds.update(init_paged_cache_inputs(cfg, N_BLOCKS, PAGE,
                                             kv_dtype=kv_dtype))
    assert set(feeds) == set(graph.inputs)
    return feeds


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_prefill_logits_are_the_per_position_last_rows(variant):
    cfg, kv_dtype = VARIANTS[variant]
    graph = _graph(cfg, kv_dtype)
    assert graph.outputs[0] == "logits"
    feeds = _feeds(cfg, kv_dtype, graph)
    got = compile(graph)(**feeds)
    want = compile(_per_position(graph))(**feeds)
    rows, every = np.asarray(got[0]), np.asarray(want[0])
    assert rows.shape == (B, cfg.vocab)
    assert every.shape == (B, T, cfg.vocab)
    # an idle slot's row is its row 0, whatever it holds
    np.testing.assert_array_equal(rows, every[np.arange(B),
                                              np.maximum(N_NEW - 1, 0)])
    # the caches (and an MoE model's counts) are as the per-position
    # graph leaves them
    for a, b in zip(got[1:], want[1:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_decode_graph_keeps_its_head():
    """The decode step takes no row gather: its one row is the row."""
    g = build_decode_graph(TINY, init_lm_params(TINY, 0), batch=B,
                           cache_cap=16)
    assert "last_new_row" not in {n.op for n in g.nodes}


def test_last_new_row_op():
    x = np.arange(3 * 5 * 2, dtype=np.float32).reshape(3, 5, 2)
    n_new = np.asarray([5, 0, 2], np.int32)
    out = np.asarray(last_new_row(x, n_new))
    np.testing.assert_array_equal(out, x[[0, 1, 2], [4, 0, 1]][:, None])
    op = get_op("last_new_row")
    specs = [TensorSpec((3, 5, 2)), TensorSpec((3,), "int32")]
    assert [s.shape for s in op.shape_fn(specs, {})] == [(3, 1, 2)]
    assert op.cost_fn(specs, {}).bytes == 2 * 3 * 2 * 4 + 3 * 4
    with pytest.raises(ValueError, match="last_new_row"):
        op.shape_fn([TensorSpec((3, 5, 2)), TensorSpec((4,), "int32")], {})


def test_last_row_logits_addressing():
    rows = np.arange(3 * 4, dtype=np.float32).reshape(3, 4)
    out = LastRowLogits(rows, np.asarray([2, 0, 5], np.int32))
    np.testing.assert_array_equal(out[0, 1], rows[0])
    np.testing.assert_array_equal(out[np.int64(2), np.int32(4)], rows[2])
    for key in [(0, 0), (0, -1), (2, 3), (2, 5), (1, -1), (1, 0), 0]:
        with pytest.raises(IndexError):
            out[key]


@pytest.mark.parametrize("variant", ["dense", "paged-fp32", "paged-int8"])
def test_stepper_returns_last_rows(variant):
    """Through the engine's stepper: the last new row of each live slot
    is served, and no other row."""
    engine, _ = make_engine(variant)
    st = engine.stepper
    b, c = st.n_slots, st.chunk
    n_new = np.asarray([c, 0, 2][:b], np.int32)
    tokens = np.random.default_rng(3).integers(
        0, TINY.vocab, (b, c)).astype(np.int32)
    if st.paged:
        for s in np.flatnonzero(n_new):
            sid, reused = st.try_admit(tokens[s, :n_new[s]], 1)
            assert reused == 0
            st.attach(int(s), sid)
    out = st.prefill(tokens, np.zeros((b,), np.int32), n_new)
    assert out.rows.shape == (b, TINY.vocab)
    assert out[0, c - 1].shape == (TINY.vocab,)
    assert np.isfinite(out[2, 1]).all()
    for key in [(0, 0), (2, 0), (1, 0), (1, -1)]:
        with pytest.raises(IndexError):
            out[key]


@pytest.mark.parametrize("variant", ["dense", "paged-fp32", "paged-int8"])
def test_engine_tokens_match_reference(variant):
    """Prompts of one row, of a whole chunk, of several chunks and of a
    ragged last chunk: the engine's greedy tokens equal the reference's."""
    engine, ref = make_engine(variant)
    c = engine.chunk
    rng = np.random.default_rng(11)
    reqs = [EngineRequest(uid=i, max_new_tokens=5,
                          prompt=rng.integers(0, TINY.vocab, n)
                          .astype(np.int32))
            for i, n in enumerate([1, c, 2 * c, 2 * c + 3, c - 1])]
    for r in reqs:
        assert engine.submit(r)
    engine.run(max_ticks=2000)
    for r in reqs:
        assert r.done and r.dropped is None, (r.uid, r.dropped)
        assert r.out_tokens == ref.generate(r.prompt, r.max_new_tokens)
