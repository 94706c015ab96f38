"""Serving a mixture-of-experts model with sliding-window layers.

The engine (``build_lm_serving(paged=True)``, the path the benchmark
takes) is compared on logits with the plain reference of the family
(``bench/models/moe_lm.py``: every expert computed for every token, the
unchosen ones weighted zero, dense windowed attention; it imports
nothing of ``src/repro``), through prefill and then paged decode past
the window, with the router as drawn and with every token forced onto
the same experts.  Then the routed-expert op's backends against each
other, the windowed paged attention ops against a numpy oracle, the
per-expert counters, and each combination the engine refuses.
"""

import importlib.util
import os

import jax.numpy as jnp
import numpy as np
import pytest
from conftest import paged_head_cases

import repro  # noqa: F401  (registers every op/backend)
from repro.core import backends_for
from repro.core.ir import TensorSpec
from repro.kernels.serving_ops import (moe_ffn, paged_chunk_attention,
                                       paged_decode_attention)
from repro.models.graph_lm import (GraphLMConfig, build_draft_graph,
                                   build_paged_decode_graph, init_lm_params)
from repro.runtime.engine import EngineRequest, build_lm_serving

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "moe_lm_reference", os.path.join(ROOT, "bench", "models", "moe_lm.py"))
moe_lm = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(moe_lm)

# four layers, sliding x3 + full, window 8, pages of 4, 8 experts, top-2,
# heads of 12 (d_model / n_heads would be 8)
TINY_MOE = GraphLMConfig(vocab=61, d_model=32, n_layers=4, n_heads=4,
                         n_kv_heads=2, head_dim=12, windows=(8, 8, 8, 0),
                         n_experts=8, top_k=2, d_expert=16,
                         matmul_precision="highest")
# the same model as the reference's configuration file states it
TINY_MOE_FILE = {
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 12, "num_hidden_layers": 4, "vocab_size": 61,
    "rms_norm_eps": 1e-6, "sliding_window": 8, "use_sliding_window": True,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "mlp_layer_types": ["sparse"] * 4, "num_experts": 8,
    "num_experts_per_tok": 2, "moe_intermediate_size": 16,
    "norm_topk_prob": True, "matmul_precision": "highest"}
# (prompt, answer) lengths: every request's context passes the window
REQUESTS = [(5, 14), (11, 12), (3, 20)]

# Both sides compute in float32 at HIGHEST: they differ only in the order
# of float32 sums (grouped experts against a sum over all experts, paged
# rows against dense ones), a few 1e-7 of logits of size ~1 (1.6e-6 at
# most seen).  2e-5 leaves ten times that; bfloat16 arithmetic (8 bits of
# mantissa) is off by ~1e-2 and a token routed to another expert by more.
LOGIT_TOL = 2e-5


def _params(force_first: bool):
    params = init_lm_params(TINY_MOE, seed=3)
    if force_first:
        # every router logit 0: the top-2 tie goes to experts 0 and 1 for
        # every token, the most skewed routing there is
        for i in range(TINY_MOE.n_layers):
            params[f"l{i}.router"] = np.zeros_like(params[f"l{i}.router"])
    return params


def _serve(params):
    """Serve ``REQUESTS`` on 3 slots; returns the requests, the logits
    row each token was taken from, and the engine."""
    eng, _ = build_lm_serving(TINY_MOE, params=params, paged=True,
                              page_size=4, n_slots=3, chunk=4, cache_cap=48)
    st = eng.stepper
    last = {}
    for kind in ("prefill", "decode"):
        base = getattr(st, kind)

        def call(tokens, start, n_new, base=base, kind=kind):
            last["out"] = (kind, base(tokens, start, n_new), n_new)
            return last["out"][1]
        setattr(st, kind, call)
    rows = {}

    def on_token(req, tok):
        kind, out, n_new = last["out"]
        slot = next(i for i, s in enumerate(eng.slots)
                    if s is not None and s.req is req)
        rows.setdefault(req.uid, []).append(
            out[slot] if kind == "decode" else out[slot, n_new[slot] - 1])

    rng = np.random.default_rng(0)
    reqs = [EngineRequest(uid=u, max_new_tokens=m, on_token=on_token,
                          prompt=rng.integers(0, 61, p).astype(np.int32))
            for u, (p, m) in enumerate(REQUESTS)]
    for r in reqs:
        assert eng.submit(r)
    eng.run()
    return reqs, rows, eng


@pytest.mark.parametrize("routing", ["drawn", "all_to_first"])
def test_engine_matches_the_plain_reference_past_the_window(routing):
    params = _params(routing == "all_to_first")
    reqs, rows, eng = _serve(params)
    ref_params = {k: jnp.asarray(v) for k, v in params.items()}
    for r in reqs:
        assert r.done and len(r.out_tokens) == r.max_new_tokens
        seq = np.concatenate([r.prompt, np.asarray(r.out_tokens[:-1],
                                                   np.int32)])
        assert len(seq) > 8          # decoded past the window
        want = np.asarray(moe_lm.forward(TINY_MOE_FILE, ref_params,
                                         jnp.asarray(seq)))[len(r.prompt) - 1:]
        np.testing.assert_allclose(np.stack(rows[r.uid]), want, rtol=0,
                                   atol=LOGIT_TOL)
    # every live row reached both its experts: rows x top_k per layer
    live = sum(len(r.prompt) + len(r.out_tokens) - 1 for r in reqs)
    counts = eng.metrics.expert_rows
    assert counts.shape == (4, 8)
    np.testing.assert_array_equal(counts.sum(axis=1), [2 * live] * 4)
    if routing == "all_to_first":
        # dropless: experts 0 and 1 took every row, none was left out
        np.testing.assert_array_equal(counts[:, :2], live)
        assert not counts[:, 2:].any()


def test_counts_hold_live_rows_only():
    """Per call, each layer's counts sum to live rows x top_k: prefill
    padding rows and idle slots are routed nowhere."""
    eng, _ = build_lm_serving(TINY_MOE, params=_params(False), paged=True,
                              page_size=4, n_slots=3, chunk=4, cache_cap=48)
    seen = []
    for kind in ("prefill", "decode"):
        base = getattr(eng.stepper, kind)

        def call(tokens, start, n_new, base=base):
            out = base(tokens, start, n_new)
            seen.append((int(np.sum(n_new)), eng.stepper.moe_counts.copy()))
            return out
        setattr(eng.stepper, kind, call)
    # one request of 6 prompt tokens on 3 slots: a full chunk, a ragged
    # one of 2 rows, then decodes with two slots idle
    eng.submit(EngineRequest(uid=0, prompt=np.arange(1, 7, dtype=np.int32),
                             max_new_tokens=4))
    eng.run()
    assert [n for n, _ in seen] == [4, 2, 1, 1, 1]
    for n, counts in seen:
        assert counts.shape == (4, 8) and counts.dtype == np.int32
        np.testing.assert_array_equal(counts.sum(axis=1), [2 * n] * 4)
    summary = eng.metrics.summary()["expert_rows"]
    assert np.sum(summary) == 4 * 2 * 9


# --------------------------------------------------------------------------- #
# the routed-expert op
# --------------------------------------------------------------------------- #

def _moe_inputs(rng, skew, t):
    b, d, e, f = 3, 16, 8, 12
    x = rng.standard_normal((b, t, d)).astype(np.float32)
    router = (rng.standard_normal((d, e)) / 4).astype(np.float32)
    if skew:
        router[:] = 0.0                # all rows tie: experts 0.. top_k-1
    ws = [(rng.standard_normal(s) / np.sqrt(s[1])).astype(np.float32)
          for s in ((e, d, f), (e, d, f), (e, f, d))]
    n_new = np.asarray([t, 2, 0], np.int32)   # full, ragged, idle
    return x, n_new, router, ws


@pytest.mark.parametrize("skew", [False, True], ids=["drawn", "skewed"])
@pytest.mark.parametrize("norm_topk", [True, False])
@pytest.mark.parametrize("top_k", [1, 3])
@pytest.mark.parametrize("t", [5, 50], ids=["batched", "grouped"])
def test_moe_ffn_xla_matches_ref(skew, norm_topk, top_k, t):
    """At 150 rows (more than a tile of the TPU's grouped kernel) the xla
    backend groups the (row, expert) pairs; at 15 (a decode-sized batch)
    it takes the oracle's every-expert form, and the case checks the
    counts and the rows routed nowhere."""
    x, n_new, router, ws = _moe_inputs(np.random.default_rng(7), skew, t)
    kw = dict(top_k=top_k, norm_topk=norm_topk, precision="highest")
    y_ref, c_ref = moe_ffn(x, n_new, router, *ws, backend="ref", **kw)
    y_xla, c_xla = moe_ffn(x, n_new, router, *ws, backend="xla", **kw)
    np.testing.assert_allclose(np.asarray(y_xla), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(c_xla), np.asarray(c_ref))
    assert int(np.sum(c_xla)) == top_k * (t + 2)            # live rows only
    # padding rows and the idle slot get nothing
    y = np.asarray(y_xla)
    assert not y[1, 2:].any() and not y[2].any()
    if skew:
        np.testing.assert_array_equal(np.asarray(c_xla)[0, :top_k], t + 2)


def test_moe_ffn_refuses_bad_shapes():
    from repro.core.registry import get_op
    x, n, r = TensorSpec((2, 3, 16)), TensorSpec((2,), "int32"), \
        TensorSpec((16, 8))
    w, wd = TensorSpec((8, 16, 12)), TensorSpec((8, 12, 16))
    shape = get_op("moe_ffn").shape_fn
    assert shape([x, n, r, w, w, wd], {"top_k": 2})[1].shape == (1, 8)
    with pytest.raises(ValueError):
        shape([x, n, r, w, w, wd], {"top_k": 9})
    with pytest.raises(ValueError):
        shape([x, n, r, w, w, w], {"top_k": 2})


# --------------------------------------------------------------------------- #
# windowed paged attention
# --------------------------------------------------------------------------- #

def _window_layout(rng, hk, d, page=4, cap=24, n_blocks=24):
    """Dense K/V rows (B, cap, Hk, D) and the same rows in a flat page
    pool under a scrambled block table."""
    b = 2
    perm = rng.permutation(n_blocks)
    mp = cap // page
    tables = perm[:b * mp].reshape(b, mp).astype(np.int32)
    dk = rng.standard_normal((b, cap, hk, d)).astype(np.float32)
    dv = rng.standard_normal((b, cap, hk, d)).astype(np.float32)
    pk = np.zeros((n_blocks, page, hk * d), np.float32)
    pv = np.zeros_like(pk)
    for bi in range(b):
        for pi in range(mp):
            rows = slice(pi * page, (pi + 1) * page)
            pk[tables[bi, pi]] = dk[bi, rows].reshape(page, -1)
            pv[tables[bi, pi]] = dv[bi, rows].reshape(page, -1)
    return dk, dv, pk, pv, tables


def _oracle(q, k, v, qpos, window):
    """numpy attention of queries at absolute positions ``qpos`` (B, T)
    over keys 0..S-1: key j is seen iff 0 <= i - j < window."""
    b, t, hq, d = q.shape
    g = hq // k.shape[2]
    kk = np.repeat(k, g, axis=2).astype(np.float64)
    vv = np.repeat(v, g, axis=2).astype(np.float64)
    s = np.einsum("bqhd,bkhd->bhqk", q.astype(np.float64), kk) / np.sqrt(d)
    back = qpos[:, None, :, None] - np.arange(k.shape[1])[None, None, None]
    s = np.where((back >= 0) & (back < window), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, vv)


@pytest.mark.parametrize("backend,hq,hk,d", paged_head_cases("ref", "xla"))
@pytest.mark.parametrize("window", [3, 8])
def test_windowed_paged_decode_attention(backend, hq, hk, d, window):
    rng = np.random.default_rng(5)
    dk, dv, pk, pv, tables = _window_layout(rng, hk, d)
    q = rng.standard_normal((2, hq, d)).astype(np.float32)
    lengths = np.asarray([20, 6], np.int32)     # the new row is lengths-1
    got = paged_decode_attention(q, pk, pv, tables, lengths, backend=backend,
                                 window=window, precision="highest")
    want = _oracle(q[:, None], dk, dv, (lengths - 1)[:, None], window)[:, 0]
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("backend,hq,hk,d", paged_head_cases("ref", "xla"))
@pytest.mark.parametrize("window", [3, 8])
def test_windowed_paged_chunk_attention(backend, hq, hk, d, window):
    rng = np.random.default_rng(6)
    dk, dv, pk, pv, tables = _window_layout(rng, hk, d)
    q = rng.standard_normal((2, 4, hq, d)).astype(np.float32)
    start = np.asarray([13, 2], np.int32)
    got = paged_chunk_attention(q, pk, pv, tables, start, backend=backend,
                                window=window, precision="highest")
    qpos = start[:, None] + np.arange(4)[None]
    want = _oracle(q, dk, dv, qpos, window)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("op", ["paged_decode_attention",
                                "paged_chunk_attention"])
def test_a_backend_without_windows_refuses_them(op):
    q = TensorSpec((1, 4, 8) if op == "paged_decode_attention"
                   else (1, 8, 4, 8))
    pool = TensorSpec((8, 8, 16))
    specs = [q, pool, pool, TensorSpec((1, 4), "int32"),
             TensorSpec((1,), "int32")]
    assert "pallas" in backends_for(op, specs, {})
    windowed = backends_for(op, specs, {"window": 4})
    assert "pallas" not in windowed and {"ref", "xla"} <= set(windowed)


# --------------------------------------------------------------------------- #
# what the engine refuses
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("kw", [
    {"paged": True, "quantize": "int8"},
    {"paged": True, "kv_dtype": "int8"},
    {"paged": True, "spec_k": 2},
    {"paged": True, "tp": 2},
    {"paged": False},
], ids=["int8-weights", "int8-kv", "spec", "tp", "dense-layout"])
def test_unsupported_combinations_refuse_at_build(kw):
    with pytest.raises(ValueError, match="dense|window"):
        build_lm_serving(TINY_MOE, n_slots=2, chunk=4, cache_cap=16,
                         page_size=4, **kw)


def test_experts_without_windows_serve_on_the_dense_layout_too():
    cfg = GraphLMConfig(vocab=61, d_model=32, n_layers=2, n_heads=4,
                        n_kv_heads=2, n_experts=4, top_k=2, d_expert=16)
    eng, ref = build_lm_serving(cfg, n_slots=2, chunk=4, cache_cap=32)
    req = EngineRequest(uid=0, prompt=np.arange(1, 7, dtype=np.int32),
                        max_new_tokens=5)
    eng.submit(req)
    eng.run()
    assert req.out_tokens == ref.generate(req.prompt, 5, chunk=4)


@pytest.mark.parametrize("build", ["kv8_decode", "draft", "config"])
def test_graph_builders_refuse_what_they_lack(build):
    params = init_lm_params(TINY_MOE)
    with pytest.raises(ValueError):
        if build == "kv8_decode":
            build_paged_decode_graph(TINY_MOE, params, batch=2, n_blocks=8,
                                     page_size=4, max_pages=4,
                                     kv_dtype="int8")
        elif build == "draft":
            build_draft_graph(TINY_MOE, params, batch=2, cache_cap=16,
                              spec_k=2)
        else:
            GraphLMConfig(n_layers=2, windows=(8, 8, 8))
