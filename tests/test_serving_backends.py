"""Serving-op backend parity: ref vs xla vs pallas(interpret) must agree
across the edge shapes the engine actually produces — idle slots
(``n_new == 0``), chunks landing exactly at cache capacity
(``start + T == cap``), GQA head ratios, and the ``scale=0.0`` regression
(an explicit falsy scale must mean "uniform attention", not "use the
default") — plus the selection plumbing: graph-LM serving Programs
compile under cost-model and autotune policies, and serving-op shapes
land in the persistent autotune cache.
"""

import json
import math

import numpy as np
import pytest
from conftest import paged_head_cases

import repro  # noqa: F401  (registers every op/backend)
from repro.core import (AutotunePolicy, CostModelPolicy, FixedPolicy,
                        backends_for, compile)
from repro.kernels.ops import decode_attention
from repro.kernels.serving_ops import cache_update, chunk_attention, embedding
from repro.models.graph_lm import (GraphLMConfig, build_decode_graph,
                                   build_prefill_graph, init_lm_params)

CFG = GraphLMConfig(vocab=37, d_model=16, n_layers=1, n_heads=4, n_kv_heads=2,
                    d_ff=32)


def _rng():
    return np.random.default_rng(7)


# --------------------------------------------------------------------------- #
# embedding
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("ids_shape", [(3, 5), (2, 1)])
def test_embedding_xla_exact(ids_shape):
    rng = _rng()
    ids = rng.integers(0, 11, size=ids_shape).astype(np.int32)
    table = rng.standard_normal((11, 8)).astype(np.float32)
    ref = np.asarray(embedding(ids, table, backend="ref"))
    xla = np.asarray(embedding(ids, table, backend="xla"))
    # a 0/1 one-hot matmul selects rows bit-for-bit
    np.testing.assert_array_equal(ref, xla)


# --------------------------------------------------------------------------- #
# cache_update — bitwise parity (pure data movement)
# --------------------------------------------------------------------------- #

def _cache_case(start, n_new, *, cap=16, t=4, b=None, hk=2, d=4):
    rng = _rng()
    b = b or len(start)
    cache = rng.standard_normal((b, cap, hk, d)).astype(np.float32)
    new = rng.standard_normal((b, t, hk, d)).astype(np.float32)
    return (cache, new, np.asarray(start, np.int32),
            np.asarray(n_new, np.int32))


@pytest.mark.parametrize("start,n_new", [
    ([0, 5, 12], [4, 4, 4]),     # last slot writes up to exactly cap
    ([0, 3, 7], [0, 0, 0]),      # all idle: exact no-op
    ([2, 12, 0], [1, 4, 3]),     # ragged chunk fills, one at capacity edge
])
def test_cache_update_xla_exact(start, n_new):
    cache, new, s, n = _cache_case(start, n_new)
    ref = np.asarray(cache_update(cache, new, s, n, backend="ref"))
    xla = np.asarray(cache_update(cache, new, s, n, backend="xla"))
    np.testing.assert_array_equal(ref, xla)


def test_cache_update_idle_slot_untouched():
    cache, new, s, n = _cache_case([0, 5], [4, 0])
    for backend in ("ref", "xla"):
        out = np.asarray(cache_update(cache, new, s, n, backend=backend))
        np.testing.assert_array_equal(out[1], cache[1])


# --------------------------------------------------------------------------- #
# chunk_attention — ref vs xla vs pallas(interpret)
# --------------------------------------------------------------------------- #

def _chunk_case(*, b=2, t=4, s=16, hq=4, hk=2, d=8, start=(0, 12)):
    rng = _rng()
    q = rng.standard_normal((b, t, hq, d)).astype(np.float32)
    k = rng.standard_normal((b, s, hk, d)).astype(np.float32)
    v = rng.standard_normal((b, s, hk, d)).astype(np.float32)
    return q, k, v, np.asarray(start, np.int32)


@pytest.mark.parametrize("hq,hk", [(1, 1), (2, 1), (4, 2), (4, 4)])
@pytest.mark.parametrize("scale", [None, 0.0])
def test_chunk_attention_backend_parity(hq, hk, scale):
    # start=12 with t=4 and s=16: the chunk's last query sits at the final
    # cache position (start + T == capacity)
    q, k, v, start = _chunk_case(hq=hq, hk=hk, start=(0, 12))
    ref = np.asarray(chunk_attention(q, k, v, start, scale=scale,
                                     backend="ref"))
    for backend in ("xla", "pallas"):
        assert backend in backends_for("chunk_attention")
        out = np.asarray(chunk_attention(q, k, v, start, scale=scale,
                                         backend=backend, interpret=True))
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5,
                                   err_msg=f"{backend} vs ref")


def test_chunk_attention_scale_zero_is_uniform():
    """Regression for `attrs.get("scale") or default`: scale=0.0 must give
    uniform attention over the allowed positions, on every backend."""
    q, k, v, start = _chunk_case(b=1, t=2, s=8, hq=2, hk=2, start=(3,))
    outs = {b: np.asarray(chunk_attention(q, k, v, start, scale=0.0,
                                          backend=b, interpret=True))
            for b in ("ref", "xla", "pallas")}
    # expected: plain mean of v rows 0..start+t (per query position)
    for t_i in range(2):
        n_allowed = 3 + t_i + 1
        want = v[0, :n_allowed].mean(axis=0)        # (Hk, D) == (Hq, D) here
        for b, out in outs.items():
            np.testing.assert_allclose(out[0, t_i], want, rtol=2e-5,
                                       atol=2e-5, err_msg=b)
    # and it must differ from the default 1/sqrt(d) scaling
    default = np.asarray(chunk_attention(q, k, v, start, backend="ref"))
    assert not np.allclose(outs["ref"], default)


def test_chunk_attention_pallas_supports_guard():
    # T=3 with block_q=2 -> 3 % 2 != 0 -> pallas must be filtered out
    from repro.core.ir import TensorSpec
    specs = [TensorSpec((1, 3, 2, 8)), TensorSpec((1, 16, 1, 8)),
             TensorSpec((1, 16, 1, 8)), TensorSpec((1,), "int32")]
    avail = backends_for("chunk_attention", specs, {"block_q": 2})
    assert "pallas" not in avail and {"ref", "xla"} <= set(avail)


# --------------------------------------------------------------------------- #
# decode_attention — split-KV backend
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("hq,hk", [(2, 1), (4, 2)])
@pytest.mark.parametrize("n_splits", [2, 4])
def test_decode_split_parity(hq, hk, n_splits):
    rng = _rng()
    b, s, d = 3, 32, 8
    q = rng.standard_normal((b, hq, d)).astype(np.float32)
    k = rng.standard_normal((b, s, hk, d)).astype(np.float32)
    v = rng.standard_normal((b, s, hk, d)).astype(np.float32)
    # lengths straddling the split boundaries, incl. one shard fully empty
    lengths = np.asarray([3, s // n_splits, s], np.int32)
    ref = np.asarray(decode_attention(q, k, v, lengths, backend="ref"))
    split = np.asarray(decode_attention(q, k, v, lengths,
                                        backend="pallas_split",
                                        n_splits=n_splits, interpret=True))
    np.testing.assert_allclose(split, ref, rtol=2e-5, atol=2e-5)


def test_decode_split_supports_guard():
    from repro.core.ir import TensorSpec
    qs = TensorSpec((1, 2, 8))
    kv_ok = TensorSpec((1, 32, 1, 8))
    kv_small = TensorSpec((1, 8, 1, 8))     # 8/2=4 < 8-row minimum shard
    lens = TensorSpec((1,), "int32")
    assert "pallas_split" in backends_for(
        "decode_attention", [qs, kv_ok, kv_ok, lens], {})
    assert "pallas_split" not in backends_for(
        "decode_attention", [qs, kv_small, kv_small, lens], {})
    assert "pallas_split" not in backends_for(
        "decode_attention", [qs, kv_ok, kv_ok, lens], {"n_splits": 3})


# --------------------------------------------------------------------------- #
# selection plumbing: serving Programs under real policies
# --------------------------------------------------------------------------- #

def _serving_ops_in(graph):
    return {n.op for n in graph.nodes} & {"embedding", "cache_update",
                                          "chunk_attention",
                                          "decode_attention",
                                          "last_new_row"}


def test_graph_lm_compiles_under_cost_model_policy():
    params = init_lm_params(CFG, 0)
    g = build_prefill_graph(CFG, params, batch=2, chunk=4, cache_cap=16)
    prog = compile(g, policy=CostModelPolicy())
    assert _serving_ops_in(prog.graph) == {"embedding", "cache_update",
                                           "chunk_attention",
                                           "last_new_row"}
    for name, backend in prog.assignment.items():
        assert backend  # every node resolved
    rng = _rng()
    (logits, *_) = prog(
        tokens=rng.integers(0, CFG.vocab, size=(2, 4)).astype(np.int32),
        start=np.zeros((2,), np.int32), n_new=np.full((2,), 4, np.int32),
        cache_k0=np.zeros((2, 16, 2, 4), np.float32),
        cache_v0=np.zeros((2, 16, 2, 4), np.float32))
    assert np.isfinite(np.asarray(logits)).all()


def test_autotune_cache_keys_serving_op_shapes(tmp_path):
    """Compiling the serving graphs under AutotunePolicy must persist
    serving-op measurements; a fresh policy preloads them and re-compiles
    with zero new measurements."""
    cache = str(tmp_path / "autotune.json")
    params = init_lm_params(CFG, 0)
    dec = build_decode_graph(CFG, params, batch=2, cache_cap=16)
    pre = build_prefill_graph(CFG, params, batch=2, chunk=4, cache_cap=16)
    # "pallas" kept in the candidate set so decode_attention (ref/pallas/
    # pallas_split) has >1 candidate and actually gets measured
    cands = ("ref", "xla", "pallas")
    pol = AutotunePolicy(reps=1, candidates=cands, cache_path=cache)
    p_dec = compile(dec, policy=pol)
    p_pre = compile(pre, policy=pol)
    assert pol.n_measured > 0
    data = json.load(open(cache))
    keys = [k for fp in data["fingerprints"].values() for k in fp]
    for op in ("embedding", "cache_update", "chunk_attention",
               "decode_attention"):
        assert any(json.loads(k)[0] == op for k in keys), f"{op} not cached"
    # chosen serving-op backends are frozen into the Programs
    for prog in (p_dec, p_pre):
        for node in prog.graph.nodes:
            if node.op in ("embedding", "cache_update", "chunk_attention",
                           "decode_attention"):
                assert prog.assignment[node.name] in cands
    # second policy: everything preloads, nothing re-measured
    pol2 = AutotunePolicy(reps=1, candidates=cands, cache_path=cache)
    assert pol2.n_loaded > 0
    compile(dec, policy=pol2)
    assert pol2.n_measured == 0


def test_engine_runs_under_fixed_pallas_policy():
    """End-to-end: the engine serves traffic with the serving ops pinned
    to the fanciest supported backends (pallas chunk attention via
    interpret on CPU, xla elsewhere)."""
    from repro.runtime.engine import EngineRequest, build_lm_serving
    policy = FixedPolicy(
        prefer=("xla", "ref"),
        per_op={"chunk_attention": ("pallas", "xla", "ref"),
                "decode_attention": ("pallas", "ref")})
    engine, _ = build_lm_serving(CFG, n_slots=2, chunk=4, cache_cap=16,
                                 policy=policy)
    summary = engine.stepper.backend_summary()
    assert summary["prefill"]["chunk_attention"] == {"pallas": CFG.n_layers}
    rng = _rng()
    reqs = [EngineRequest(uid=i,
                          prompt=rng.integers(0, CFG.vocab, size=3 + i)
                          .astype(np.int32),
                          max_new_tokens=3)
            for i in range(3)]
    for r in reqs:
        assert engine.submit(r)
    engine.run(max_ticks=500)
    assert all(r.done and len(r.out_tokens) == 3 for r in reqs)


# --------------------------------------------------------------------------- #
# the verify shape (speculative decoding) — a verify step is a chunk of
# T = K+1 rows through chunk_attention / paged_chunk_attention, so every
# backend must hold at that shape, and the explicit-zero scale with it
# (the `attrs.get(...) or default` bug class)
# --------------------------------------------------------------------------- #

def _np_chunk_attention(q, k, v, start, scale):
    """Offset-causal attention in float64: query row t of slot b sits at
    position start[b] + t and sees keys 0 .. start[b] + t; query head h
    reads KV head h // (Hq // Hk)."""
    b, t, hq, d = q.shape
    group = hq // k.shape[2]
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    out = np.zeros(q.shape, np.float64)
    for bi in range(b):
        for ti in range(t):
            n = start[bi] + ti + 1
            for h in range(hq):
                kh = k[bi, :n, h // group].astype(np.float64)
                logits = kh @ q[bi, ti, h].astype(np.float64) * scale
                w = np.exp(logits - logits.max())
                out[bi, ti, h] = (w / w.sum()) @ v[bi, :n, h // group]
    return out


@pytest.mark.parametrize("scale", [None, 0.0, 2.0])
def test_chunk_attention_at_verify_shape(scale):
    """T = K+1 = 4 rows, one slot ending at the cache's last row; an
    explicit scale=0.0 must survive to every backend (not be swallowed by
    a falsy-default fallback)."""
    rng = _rng()
    q = rng.standard_normal((2, 4, 4, 8)).astype(np.float32)
    k = rng.standard_normal((2, 16, 2, 8)).astype(np.float32)
    v = rng.standard_normal((2, 16, 2, 8)).astype(np.float32)
    start = np.asarray([0, 12], np.int32)
    want = _np_chunk_attention(q, k, v, start, scale)
    for backend in ("ref", "xla", "pallas"):
        assert backend in backends_for("chunk_attention")
        out = np.asarray(chunk_attention(q, k, v, start, scale=scale,
                                         backend=backend, interpret=True))
        np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5,
                                   err_msg=f"{backend} scale={scale}")
    if scale == 0.0:
        dflt = np.asarray(chunk_attention(q, k, v, start, backend="ref"))
        assert not np.allclose(want, dflt), \
            "scale=0.0 was swallowed by a falsy default"


@pytest.mark.parametrize("scale,hq,hk,d", paged_head_cases(None, 0.0))
def test_paged_chunk_attention_at_verify_shape(scale, hq, hk, d):
    """T = K+1 = 4 rows read through a scrambled block table: every
    backend equals offset-causal attention over the gathered pages."""
    from repro.kernels.serving_ops import paged_chunk_attention
    rng = _rng()
    b, t, n_blocks, page, mp = 2, 4, 8, 4, 4
    q = rng.standard_normal((b, t, hq, d)).astype(np.float32)
    pk = rng.standard_normal((n_blocks, page, hk, d)).astype(np.float32)
    pv = rng.standard_normal((n_blocks, page, hk, d)).astype(np.float32)
    tables = rng.permutation(n_blocks).reshape(b, mp).astype(np.int32)
    start = np.asarray([0, 7], np.int32)
    # dense oracle: gather each sequence's pages then offset-causal chunk
    kd = np.stack([pk[tables[bi]].reshape(mp * page, hk, d)
                   for bi in range(b)])
    vd = np.stack([pv[tables[bi]].reshape(mp * page, hk, d)
                   for bi in range(b)])
    want = _np_chunk_attention(q, kd, vd, start, scale)
    flat = (n_blocks, page, hk * d)
    for backend in ("ref", "xla", "pallas"):
        assert backend in backends_for("paged_chunk_attention")
        out = np.asarray(paged_chunk_attention(
            q, pk.reshape(flat), pv.reshape(flat), tables, start,
            scale=scale, backend=backend, interpret=True))
        np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5,
                                   err_msg=f"{backend} scale={scale}")


def test_chunk_attention_pallas_guards_at_verify_shape():
    """A ragged verify width (T = 3 with block_q = 2) must filter the
    pallas paths of both chunk ops out, never crash them."""
    from repro.core.ir import TensorSpec
    dense = [TensorSpec((1, 3, 2, 8)), TensorSpec((1, 16, 1, 8)),
             TensorSpec((1, 16, 1, 8)), TensorSpec((1,), "int32")]
    avail = backends_for("chunk_attention", dense, {"block_q": 2})
    assert "pallas" not in avail and {"ref", "xla"} <= set(avail)
    paged = [TensorSpec((1, 3, 2, 8)), TensorSpec((8, 8, 8)),
             TensorSpec((8, 8, 8)), TensorSpec((1, 4), "int32"),
             TensorSpec((1,), "int32")]
    avail = backends_for("paged_chunk_attention", paged, {"block_q": 2})
    assert "pallas" not in avail and {"ref", "xla"} <= set(avail)
    # the same specs at an even width keep pallas, so the guard is T's
    paged[0] = TensorSpec((1, 4, 2, 8))
    assert "pallas" in backends_for("paged_chunk_attention", paged,
                                    {"block_q": 2})


def test_greedy_token_argmax():
    from repro.kernels.serving_ops import greedy_token
    rng = _rng()
    logits = rng.standard_normal((3, 37)).astype(np.float32)
    out = np.asarray(greedy_token(logits))
    # (B, 1) int32 — shaped to feed straight back as the next tokens column
    assert out.shape == (3, 1) and out.dtype == np.int32
    np.testing.assert_array_equal(out[:, 0], np.argmax(logits, axis=-1))
