"""Paged serving stack: op parity, engine exactness, block admission.

The exactness bar for the paged KV cache is strict: the paged engine's
greedy output must be TOKEN-IDENTICAL to the dense
:class:`~repro.runtime.engine.UnbatchedReference` for fp32 and int8
Programs, with and without prefix hits, including the copy-on-write
divergence path (concurrent requests sharing a cached partial tail
page).  Backend parity pins the paged ops (ref / xla / pallas-interpret)
against their dense equivalents on a scrambled physical block layout.
"""

import numpy as np
import pytest
from conftest import PAGED_HEADS, paged_head_cases

import repro  # noqa: F401  (registers every op/backend)
from repro.core import backends_for
from repro.core.ir import TensorSpec
from repro.kernels.ops import decode_attention
from repro.kernels.serving_ops import (cache_update, chunk_attention,
                                       paged_cache_update,
                                       paged_chunk_attention,
                                       paged_decode_attention)
from repro.models.graph_lm import GraphLMConfig
from repro.runtime.engine import EngineRequest, build_lm_serving

TINY = GraphLMConfig(vocab=61, d_model=32, n_layers=2, n_heads=4,
                     n_kv_heads=2, d_ff=64)


def _rng():
    return np.random.default_rng(3)


def _paged_layout(rng, *, b=3, cap=16, hk=2, d=8, n_blocks=10, page=4,
                  lengths=(14, 9, 5)):
    """A dense cache plus the equivalent flat (N, P, Hk*D) paged layout
    under a scrambled block mapping (so parity failures can't hide behind
    identity maps)."""
    perm = rng.permutation(n_blocks)
    mp = cap // page
    tables = np.zeros((b, mp), np.int32)
    used = iter(perm)
    dense_k = rng.standard_normal((b, cap, hk, d)).astype(np.float32)
    dense_v = rng.standard_normal((b, cap, hk, d)).astype(np.float32)
    pages_k = np.zeros((n_blocks, page, hk, d), np.float32)
    pages_v = np.zeros((n_blocks, page, hk, d), np.float32)
    lengths = np.asarray(lengths, np.int32)
    for bi in range(b):
        for pi in range(-(-int(lengths[bi]) // page)):
            blk = int(next(used))
            tables[bi, pi] = blk
            pages_k[blk] = dense_k[bi, pi * page:(pi + 1) * page]
            pages_v[blk] = dense_v[bi, pi * page:(pi + 1) * page]
    flat = (n_blocks, page, hk * d)
    return (dense_k, dense_v, pages_k.reshape(flat), pages_v.reshape(flat),
            tables, lengths)


# --------------------------------------------------------------------------- #
# op parity vs the dense equivalents
# --------------------------------------------------------------------------- #

def test_paged_cache_update_matches_dense_rows():
    for _, hk, d in PAGED_HEADS.values():
        rng = _rng()
        dk, _, pk, _, tables, lengths = _paged_layout(rng, hk=hk, d=d)
        new = rng.standard_normal((3, 4, hk, d)).astype(np.float32)
        start, n_new = lengths.copy(), np.asarray([2, 0, 3], np.int32)
        ref = np.asarray(paged_cache_update(pk, new, tables, start, n_new,
                                            backend="ref"))
        xla = np.asarray(paged_cache_update(pk, new, tables, start, n_new,
                                            backend="xla"))
        np.testing.assert_array_equal(ref, xla)
        dense = np.asarray(cache_update(dk, new, start, n_new,
                                        backend="ref"))
        for bi in range(3):
            for t in range(int(n_new[bi])):
                pos = int(start[bi]) + t
                # head h of the row sits in lanes h*D .. h*D+D-1
                np.testing.assert_array_equal(
                    ref[tables[bi, pos // 4], pos % 4],
                    dense[bi, pos].reshape(-1))
        # idle slot's pages untouched
        np.testing.assert_array_equal(ref[tables[1, 0]], pk[tables[1, 0]])


@pytest.mark.parametrize("backend,hq,hk,d",
                         paged_head_cases("ref", "xla", "pallas"))
def test_paged_decode_attention_parity(backend, hq, hk, d):
    rng = _rng()
    dk, dv, pk, pv, tables, lengths = _paged_layout(rng, hk=hk, d=d)
    q = rng.standard_normal((3, hq, d)).astype(np.float32)
    want = np.asarray(decode_attention(q, dk, dv, lengths, backend="ref"))
    got = np.asarray(paged_decode_attention(q, pk, pv, tables, lengths,
                                            backend=backend, interpret=True))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("backend,hq,hk,d",
                         paged_head_cases("ref", "xla", "pallas"))
@pytest.mark.parametrize("scale", [None, 0.0])
def test_paged_chunk_attention_parity(backend, hq, hk, d, scale):
    rng = _rng()
    dk, dv, pk, pv, tables, _ = _paged_layout(rng, hk=hk, d=d)
    q = rng.standard_normal((3, 4, hq, d)).astype(np.float32)
    start = np.asarray([10, 4, 1], np.int32)
    want = np.asarray(chunk_attention(q, dk, dv, start, scale=scale,
                                      backend="ref"))
    got = np.asarray(paged_chunk_attention(q, pk, pv, tables, start,
                                           scale=scale, backend=backend,
                                           interpret=True))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_paged_decode_pallas_supports_guard():
    qs = TensorSpec((1, 4, 8))
    tb = TensorSpec((1, 4), "int32")
    ln = TensorSpec((1,), "int32")
    ok = TensorSpec((8, 8, 16))         # page 8 % 8 == 0
    bad = TensorSpec((8, 6, 16))        # page 6 % 8 != 0
    assert "pallas" in backends_for("paged_decode_attention",
                                    [qs, ok, ok, tb, ln], {})
    avail = backends_for("paged_decode_attention", [qs, bad, bad, tb, ln], {})
    assert "pallas" not in avail and {"ref", "xla"} <= set(avail)


def test_dense_cache_update_ragged_final_chunk_parity():
    """start > cap - T with start + n_new <= cap (a ragged final chunk
    ending exactly at capacity): both backends must write rows at the true
    positions.  The ref backend used to clip padding rows onto cap-1 and
    corrupt it via a duplicate-index scatter."""
    rng = _rng()
    cache = rng.standard_normal((2, 16, 2, 8)).astype(np.float32)
    new = rng.standard_normal((2, 4, 2, 8)).astype(np.float32)
    start = np.asarray([14, 13], np.int32)
    n_new = np.asarray([2, 3], np.int32)
    ref = np.asarray(cache_update(cache, new, start, n_new, backend="ref"))
    xla = np.asarray(cache_update(cache, new, start, n_new, backend="xla"))
    np.testing.assert_array_equal(ref, xla)
    np.testing.assert_array_equal(ref[0, 14:16], new[0, :2])
    np.testing.assert_array_equal(ref[1, 13:16], new[1, :3])
    np.testing.assert_array_equal(ref[0, :14], cache[0, :14])


# --------------------------------------------------------------------------- #
# engine end-to-end: paged vs dense reference
# --------------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def paged_fp32():
    return build_lm_serving(TINY, n_slots=3, chunk=4, cache_cap=48,
                            paged=True, page_size=8)


@pytest.fixture(scope="module")
def paged_int8():
    return build_lm_serving(TINY, n_slots=2, chunk=4, cache_cap=32,
                            paged=True, page_size=8, quantize="int8")


def _req(uid, rng, *, max_prompt=13, max_new=7):
    plen = int(rng.integers(1, max_prompt))
    return EngineRequest(uid=uid,
                         prompt=rng.integers(0, TINY.vocab,
                                             size=plen).astype(np.int32),
                         max_new_tokens=int(rng.integers(1, max_new)))


def _exact(engine, ref, reqs):
    for r in reqs:
        assert engine.submit(r), r.dropped
    engine.run(max_ticks=4000)
    for r in reqs:
        assert r.done and r.dropped is None, (r.uid, r.dropped)
        want = ref.generate(r.prompt, r.max_new_tokens)
        assert r.out_tokens == want, (r.uid, r.out_tokens, want)
    engine.sched.check_conservation()
    engine.stepper.pool.check_integrity()


def test_paged_engine_token_exact_fp32_cold(paged_fp32):
    engine, ref = paged_fp32
    rng = np.random.default_rng(11)
    _exact(engine, ref, [_req(i, rng) for i in range(7)])
    assert engine.stepper.pool.stats()["live_blocks"] == 0


def test_paged_engine_prefix_hit_exact_and_faster(paged_fp32):
    """A prompt whose prefix is cached must (a) register a hit, (b) stay
    token-exact, (c) finish prefill in fewer ticks than the cold run."""
    engine, ref = paged_fp32
    rng = np.random.default_rng(12)
    prefix = rng.integers(0, TINY.vocab, size=24).astype(np.int32)
    cold = EngineRequest(uid=100, prompt=np.concatenate(
        [prefix, rng.integers(0, TINY.vocab, size=3).astype(np.int32)]),
        max_new_tokens=5)
    assert engine.submit(cold)
    engine.run(max_ticks=500)
    cold_prefill_ticks = (cold.first_token_tick or 0) - cold.submit_tick
    hits_before = engine.stepper.pool.hit_tokens
    warm = EngineRequest(uid=101, prompt=np.concatenate(
        [prefix, rng.integers(0, TINY.vocab, size=2).astype(np.int32)]),
        max_new_tokens=5)
    assert engine.submit(warm)
    engine.run(max_ticks=500)
    assert engine.stepper.pool.hit_tokens - hits_before >= 24
    assert warm.out_tokens == ref.generate(warm.prompt, 5)
    warm_prefill_ticks = (warm.first_token_tick or 0) - warm.submit_tick
    assert warm_prefill_ticks < cold_prefill_ticks, \
        (warm_prefill_ticks, cold_prefill_ticks)


def test_paged_engine_cow_divergence_exact(paged_fp32):
    """Concurrent requests sharing a cached PARTIAL tail page: each one's
    first write into the shared page must copy-on-write, and every stream
    must stay token-exact."""
    engine, ref = paged_fp32
    rng = np.random.default_rng(13)
    pre = rng.integers(0, TINY.vocab, size=21).astype(np.int32)  # tail: 5 rows
    seed_req = EngineRequest(uid=200, prompt=pre, max_new_tokens=2)
    assert engine.submit(seed_req)
    engine.run(max_ticks=500)
    cow0 = engine.stepper.pool.cow_count
    reqs = [EngineRequest(uid=201 + i, prompt=np.concatenate(
        [pre, rng.integers(0, TINY.vocab, size=2 + i).astype(np.int32)]),
        max_new_tokens=4) for i in range(3)]
    _exact(engine, ref, reqs)
    assert engine.stepper.pool.cow_count > cow0, "CoW never fired"


def test_paged_engine_token_exact_int8(paged_int8):
    engine, ref = paged_int8
    from repro.core.quant import is_quantized
    assert is_quantized(engine.stepper.decode_program.graph)
    assert is_quantized(engine.stepper.prefill_program.graph)
    rng = np.random.default_rng(14)
    reqs = [_req(i, rng, max_prompt=11, max_new=5) for i in range(5)]
    _exact(engine, ref, reqs)
    # prefix hit under int8
    warm = EngineRequest(uid=50, prompt=np.concatenate(
        [reqs[0].prompt, reqs[0].prompt[:2]]), max_new_tokens=3)
    hits0 = engine.stepper.pool.hit_tokens
    _exact(engine, ref, [warm])
    assert engine.stepper.pool.hit_tokens >= hits0


def test_block_admission_defers_then_drains():
    """More worst-case demand than the pool holds: admission must wait on
    BLOCK availability (not slot count), then drain everything exactly."""
    engine, ref = build_lm_serving(TINY, n_slots=4, chunk=4, cache_cap=32,
                                   paged=True, page_size=8, n_blocks=5)
    rng = np.random.default_rng(15)
    # each request reserves pages_needed(8, 9) = 2 pages of 8, so only two
    # fit the 5-block pool at once: slots 3 and 4 sit free while admission
    # waits on blocks — the thing this test is about
    reqs = [EngineRequest(uid=i,
                          prompt=rng.integers(0, TINY.vocab, size=8)
                          .astype(np.int32),
                          max_new_tokens=9) for i in range(6)]
    for r in reqs:
        assert engine.submit(r)
    engine.step()
    assert 0 < engine.sched.busy_slots, "nothing admitted"
    engine.run(max_ticks=4000)
    for r in reqs:
        assert r.done and r.out_tokens == ref.generate(r.prompt, 9), r.uid
    assert engine.stepper.pool.n_admit_deferred > 0, \
        "admission was never block-limited"
    engine.stepper.pool.check_integrity()


def test_submit_rejects_what_can_never_fit():
    engine, _ = build_lm_serving(TINY, n_slots=2, chunk=4, cache_cap=32,
                                 paged=True, page_size=8, n_blocks=6)
    # per-sequence cap: 32 rows
    too_long = EngineRequest(uid=1, prompt=np.zeros(30, np.int32),
                             max_new_tokens=4)
    assert not engine.submit(too_long) and too_long.dropped == "too_long"
    # fits the table but not the whole pool? cap 32 = 4 pages <= 6 blocks,
    # so the boundary case is admissible
    edge = EngineRequest(uid=2, prompt=np.zeros(32, np.int32),
                         max_new_tokens=1)
    assert engine.submit(edge)
    engine.run(max_ticks=500)
    assert edge.done


# --------------------------------------------------------------------------- #
# selection plumbing: the paged ops are first-class registry citizens
# --------------------------------------------------------------------------- #

def test_paged_graph_compiles_under_cost_model_policy():
    from repro.core import CostModelPolicy, compile
    from repro.models.graph_lm import (build_paged_prefill_graph,
                                       init_lm_params)
    cfg = GraphLMConfig(vocab=37, d_model=16, n_layers=1, n_heads=4,
                        n_kv_heads=2, d_ff=32)
    params = init_lm_params(cfg, 0)
    g = build_paged_prefill_graph(cfg, params, batch=2, chunk=4,
                                  n_blocks=8, page_size=4, max_pages=4)
    prog = compile(g, policy=CostModelPolicy())
    ops = {n.op for n in prog.graph.nodes}
    assert {"paged_cache_update", "paged_chunk_attention"} <= ops
    rng = _rng()
    (logits, *_) = prog(
        tokens=rng.integers(0, 37, size=(2, 4)).astype(np.int32),
        start=np.zeros((2,), np.int32), n_new=np.full((2,), 4, np.int32),
        block_tables=np.asarray([[0, 1, 0, 0], [2, 3, 0, 0]], np.int32),
        cache_k0=np.zeros((8, 4, 2 * 4), np.float32),
        cache_v0=np.zeros((8, 4, 2 * 4), np.float32))
    assert np.isfinite(np.asarray(logits)).all()


def test_autotune_cache_keys_paged_op_shapes(tmp_path):
    import json
    from repro.core import AutotunePolicy, compile
    from repro.models.graph_lm import build_paged_decode_graph, init_lm_params
    cfg = GraphLMConfig(vocab=37, d_model=16, n_layers=1, n_heads=4,
                        n_kv_heads=2, d_ff=32)
    params = init_lm_params(cfg, 0)
    g = build_paged_decode_graph(cfg, params, batch=2, n_blocks=8,
                                 page_size=8, max_pages=2)
    cache = str(tmp_path / "autotune.json")
    pol = AutotunePolicy(reps=1, candidates=("ref", "xla", "pallas"),
                         cache_path=cache)
    prog = compile(g, policy=pol)
    assert pol.n_measured > 0
    keys = [k for fp in json.load(open(cache))["fingerprints"].values()
            for k in fp]
    for op in ("paged_cache_update", "paged_decode_attention"):
        assert any(json.loads(k)[0] == op for k in keys), f"{op} not cached"
    for node in prog.graph.nodes:
        if node.op.startswith("paged_"):
            assert prog.assignment[node.name] in ("ref", "xla", "pallas")


# --------------------------------------------------------------------------- #
# the admission boundary fix (dense engine): len(prompt) == cache_cap
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("chunk", [4, 5])     # 5 does not divide 32
def test_dense_boundary_prompt_equals_cache_cap(chunk):
    engine, ref = build_lm_serving(TINY, n_slots=2, chunk=chunk,
                                   cache_cap=32)
    rng = np.random.default_rng(16)
    prompt = rng.integers(0, TINY.vocab, size=32).astype(np.int32)
    req = EngineRequest(uid=1, prompt=prompt, max_new_tokens=1)
    assert engine.submit(req), req.dropped
    engine.run(max_ticks=200)
    assert req.done
    assert req.out_tokens == ref.generate(prompt, 1)
    assert req.out_tokens == ref.generate(prompt, 1, chunk=chunk)
    # one token longer must still be rejected
    over = EngineRequest(uid=2, prompt=prompt, max_new_tokens=2)
    assert not engine.submit(over) and over.dropped == "too_long"
