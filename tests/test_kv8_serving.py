"""int8 KV-cache pages: quantized op parity, scale bookkeeping, engine
exactness, and byte-honest pool accounting.

The quantized serving path stores paged K/V as symmetric int8 with one
float32 scale per (page, kv-head).  The bars here: the ``*_q`` ops must
match the fp32 paged ops on dequantized pages across ref / xla /
pallas-interpret on a SCRAMBLED physical block layout; writes must keep
scales monotone (requantizing quieter rows, never amplifying noise into
untouched pages); and the kv8 engine must stay token-exact against the
fp32 dense :class:`~repro.runtime.engine.UnbatchedReference` on the
cold, prefix-hit and copy-on-write paths, with logit error bounded.
"""

import numpy as np
import pytest
from conftest import TINY_LM, make_engine, paged_head_cases

import repro  # noqa: F401  (registers every op/backend)
from repro.core import backends_for, compile
from repro.core.ir import TensorSpec
from repro.kernels.serving_ops import (paged_cache_update_q,
                                       paged_chunk_attention,
                                       paged_chunk_attention_q,
                                       paged_decode_attention,
                                       paged_decode_attention_q)
from repro.models.graph_lm import (GraphLMConfig, build_paged_prefill_graph,
                                   build_prefill_graph, init_lm_params)
from repro.runtime.engine import EngineRequest, build_lm_serving
from repro.runtime.kv_cache import BlockPool, kv_page_bytes

TINY = GraphLMConfig(**TINY_LM)


def _rng():
    return np.random.default_rng(7)


def _quantize_pages(pages):
    """Symmetric per-(page, kv-head) int8 — the scheme the ops implement:
    (N, P, Hk, D) fp32 -> flat (N, P, Hk*D) int8 pages + (N, Hk) scales."""
    amax = np.abs(pages).max(axis=(1, 3))                    # (N, Hk)
    scales = (amax / 127.0).astype(np.float32)
    safe = np.where(scales > 0, scales, 1.0)
    q = np.clip(np.round(pages / safe[:, None, :, None]),
                -127, 127).astype(np.int8)
    return q.reshape(*q.shape[:2], -1), scales


def _dequant(pages_q, scales):
    """Flat (N, P, Hk*D) int8 pages -> flat fp32 pages."""
    n, p, hkd = pages_q.shape
    heads = pages_q.reshape(n, p, scales.shape[1], -1).astype(np.float32)
    return (heads * scales[:, None, :, None]).reshape(n, p, hkd)


def _q_layout(rng, *, b=3, cap=32, hk=2, d=8, n_blocks=12, page=8,
              lengths=(14, 9, 5)):
    """Quantized paged K/V under a scrambled block mapping, plus the
    dequantized fp32 pages the ``*_q`` ops must agree with.  Pools are
    flat (N, P, Hk*D)."""
    perm = rng.permutation(n_blocks)
    tables = np.zeros((b, cap // page), np.int32)
    used = iter(perm)
    pages_k = np.zeros((n_blocks, page, hk, d), np.float32)
    pages_v = np.zeros((n_blocks, page, hk, d), np.float32)
    lengths = np.asarray(lengths, np.int32)
    for bi in range(b):
        # every logical page owns a (scrambled) physical block, as the
        # engine guarantees for pages a write may touch; pages past the
        # current length hold zeros (scale 0.0)
        for pi in range(cap // page):
            blk = int(next(used))
            tables[bi, pi] = blk
            if pi * page < int(lengths[bi]):
                pages_k[blk] = rng.standard_normal((page, hk, d))
                pages_v[blk] = rng.standard_normal((page, hk, d))
    qk, sk = _quantize_pages(pages_k)
    qv, sv = _quantize_pages(pages_v)
    return qk, sk, qv, sv, tables, lengths


# --------------------------------------------------------------------------- #
# quantized write: ref/xla identity, round-trip, ragged pages, scale rules
# --------------------------------------------------------------------------- #

def test_cache_update_q_ref_xla_identical_and_roundtrips():
    """Ragged writes spanning a page boundary into a scrambled layout:
    both backends produce bit-identical pages AND scales, written rows
    dequantize back within one quantization step, and pages no slot
    touched come back bit-identical (the ratio==1 requantize path)."""
    rng = _rng()
    qk, sk, _, _, tables, lengths = _q_layout(rng)
    new = rng.standard_normal((3, 4, 2, 8)).astype(np.float32)
    start = lengths.copy()
    n_new = np.asarray([3, 0, 4], np.int32)   # slot 2 crosses rows 5..8
    ref_p, ref_s = (np.asarray(x) for x in paged_cache_update_q(
        qk, sk, new, tables, start, n_new, backend="ref"))
    xla_p, xla_s = (np.asarray(x) for x in paged_cache_update_q(
        qk, sk, new, tables, start, n_new, backend="xla"))
    np.testing.assert_array_equal(ref_p, xla_p)
    np.testing.assert_array_equal(ref_s, xla_s)
    deq = _dequant(ref_p, ref_s)
    for bi in range(3):
        for t in range(int(n_new[bi])):
            pos = int(start[bi]) + t
            blk, row = tables[bi, pos // 8], pos % 8
            tol = ref_s[blk].max() * 0.5 + 1e-7   # half a quantum per head
            np.testing.assert_allclose(deq[blk, row], new[bi, t].reshape(-1),
                                       atol=2 * tol)
    # idle slot 1: its pages and scales are bit-untouched
    for pi in range(2):
        blk = tables[1, pi]
        np.testing.assert_array_equal(ref_p[blk], qk[blk])
        np.testing.assert_array_equal(ref_s[blk], sk[blk])
    # scales only ever grow
    assert (ref_s >= sk - 1e-9).all()


def test_cache_update_q_all_zero_rows_keep_zero_scale():
    """Writing all-zero rows into a zero pool must leave scale == 0.0 (the
    sentinel for 'only zeros ever stored') and int8 zeros — and attention
    over such pages must stay finite (the falsy-scale guard: dequant is
    'treat as 0', never a division)."""
    qk = np.zeros((4, 8, 2 * 8), np.int8)
    sk = np.zeros((4, 2), np.float32)
    qv, sv = qk.copy(), sk.copy()
    tables = np.asarray([[0, 1]], np.int32)
    new = np.zeros((1, 4, 2, 8), np.float32)
    for backend in ("ref", "xla"):
        p, s = (np.asarray(x) for x in paged_cache_update_q(
            qk, sk, new, tables, np.asarray([0], np.int32),
            np.asarray([4], np.int32), backend=backend))
        assert (p == 0).all() and (s == 0.0).all()
    q = _rng().standard_normal((1, 4, 8)).astype(np.float32)
    out = np.asarray(paged_decode_attention_q(
        q, qk, sk, qv, sv, tables, np.asarray([4], np.int32), backend="ref"))
    assert np.isfinite(out).all()
    # all-zero V rows => attention output is exactly 0
    np.testing.assert_array_equal(out, np.zeros_like(out))


def test_cache_update_q_requantizes_when_scale_grows():
    """A loud row landing in a page of quiet rows must raise that page's
    scale and requantize the existing rows under it — old content still
    dequantizes to itself within the NEW (coarser) quantum."""
    rng = _rng()
    pages = np.zeros((2, 8, 2, 8), np.float32)           # (N, P, Hk, D)
    quiet = 0.05 * rng.standard_normal((1, 4, 2, 8)).astype(np.float32)
    tables = np.asarray([[0, 1]], np.int32)
    qp, sc = _quantize_pages(pages)            # all-zero start
    qp, sc = (np.asarray(x) for x in paged_cache_update_q(
        qp, sc, quiet, tables, np.asarray([0], np.int32),
        np.asarray([4], np.int32), backend="xla"))
    quiet_scale = sc.copy()
    assert (sc[0] > 0).all()
    loud = 10.0 * np.ones((1, 1, 2, 8), np.float32)
    qp2, sc2 = (np.asarray(x) for x in paged_cache_update_q(
        qp, sc, loud, tables, np.asarray([4], np.int32),
        np.asarray([1], np.int32), backend="xla"))
    assert (sc2[0] > quiet_scale[0]).all()     # grew for the loud row
    deq = _dequant(qp2, sc2)
    np.testing.assert_allclose(deq[0, :4], quiet[0].reshape(4, -1),
                               atol=sc2[0].max() + 1e-7)
    np.testing.assert_allclose(deq[0, 4], loud[0, 0].reshape(-1),
                               atol=sc2[0].max())
    # page 1 never written: still exactly zero with zero scale
    assert (qp2[1] == 0).all() and (sc2[1] == 0.0).all()


# --------------------------------------------------------------------------- #
# quantized attention parity vs the fp32 paged ops on dequantized pages
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("backend,hq,hk,d",
                         paged_head_cases("ref", "xla", "pallas"))
def test_paged_decode_attention_q_parity(backend, hq, hk, d):
    rng = _rng()
    qk, sk, qv, sv, tables, lengths = _q_layout(rng, hk=hk, d=d)
    q = rng.standard_normal((3, hq, d)).astype(np.float32)
    want = np.asarray(paged_decode_attention(
        q, _dequant(qk, sk), _dequant(qv, sv), tables, lengths,
        backend="ref"))
    got = np.asarray(paged_decode_attention_q(
        q, qk, sk, qv, sv, tables, lengths, backend=backend, interpret=True))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("backend,hq,hk,d",
                         paged_head_cases("ref", "xla", "pallas"))
def test_paged_chunk_attention_q_parity(backend, hq, hk, d):
    rng = _rng()
    qk, sk, qv, sv, tables, _ = _q_layout(rng, hk=hk, d=d)
    q = rng.standard_normal((3, 4, hq, d)).astype(np.float32)
    start = np.asarray([10, 4, 1], np.int32)
    want = np.asarray(paged_chunk_attention(
        q, _dequant(qk, sk), _dequant(qv, sv), tables, start, backend="ref"))
    got = np.asarray(paged_chunk_attention_q(
        q, qk, sk, qv, sv, tables, start, backend=backend, interpret=True))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_paged_q_pallas_supports_guards():
    """page % 8 != 0 excludes the fused pallas kernels but never the
    ref/xla dequant-after-gather fallbacks."""
    tb = TensorSpec((1, 4), "int32")
    ln = TensorSpec((1,), "int32")
    sc = TensorSpec((8, 2))
    ok = TensorSpec((8, 8, 16), "int8")
    bad = TensorSpec((8, 6, 16), "int8")
    qd = TensorSpec((1, 4, 8))
    assert "pallas" in backends_for(
        "paged_decode_attention_q", [qd, ok, sc, ok, sc, tb, ln], {})
    avail = backends_for(
        "paged_decode_attention_q", [qd, bad, sc, bad, sc, tb, ln], {})
    assert "pallas" not in avail and {"ref", "xla"} <= set(avail)
    qc = TensorSpec((1, 8, 4, 8))
    assert "pallas" in backends_for(
        "paged_chunk_attention_q", [qc, ok, sc, ok, sc, tb, ln], {})
    avail = backends_for(
        "paged_chunk_attention_q", [qc, bad, sc, bad, sc, tb, ln], {})
    assert "pallas" not in avail and {"ref", "xla"} <= set(avail)


def test_cache_update_q_rejects_bad_specs():
    """The op declaration refuses fp32 pages and mis-shaped scale
    sidecars at shape-inference time (i.e. graph build, before compile)."""
    from repro.core.registry import get_op
    shape_fn = get_op("paged_cache_update_q").shape_fn
    sc = TensorSpec((4, 2))
    new = TensorSpec((1, 2, 2, 8))
    tb = TensorSpec((1, 2), "int32")
    z = TensorSpec((1,), "int32")
    with pytest.raises(ValueError, match="int8"):
        shape_fn([TensorSpec((4, 8, 16)), sc, new, tb, z, z], {})
    pages = TensorSpec((4, 8, 16), "int8")
    with pytest.raises(ValueError, match="scales"):
        shape_fn([pages, TensorSpec((4, 1)), new, tb, z, z], {})
    # a pool row must be exactly the rows' Hk*D lanes
    with pytest.raises(ValueError, match="head mismatch"):
        shape_fn([TensorSpec((4, 8, 2, 8), "int8"), sc, new, tb, z, z], {})
    assert [s.shape for s in shape_fn([pages, sc, new, tb, z, z], {})] \
        == [(4, 8, 16), (4, 2)]


# --------------------------------------------------------------------------- #
# graph-level: bounded logit error vs the fp32 dense graph
# --------------------------------------------------------------------------- #

def test_kv8_prefill_logits_bounded_and_top1_exact():
    """A prompt prefilled through the kv8 paged graph vs the fp32 dense
    graph: max |logit error| < 0.05 and the greedy top-1 token agrees at
    EVERY position — the documented accuracy contract.  A prefill call
    returns its last new row's logits, so the chunk runs once for each
    ``n_new`` in 1..t, from empty caches."""
    cfg = TINY
    params = init_lm_params(cfg, 0)
    t, page, n_blocks = 16, 8, 6
    rng = _rng()
    toks = rng.integers(0, cfg.vocab, size=(1, t)).astype(np.int32)
    start = np.zeros((1,), np.int32)
    dense = compile(build_prefill_graph(cfg, params, batch=1, chunk=t,
                                        cache_cap=t))
    kv8 = compile(build_paged_prefill_graph(
        cfg, params, batch=1, chunk=t, n_blocks=n_blocks, page_size=page,
        max_pages=t // page, kv_dtype="int8"))
    want, got = [], []
    for n in range(1, t + 1):
        n_new = np.full((1,), n, np.int32)
        want.append(np.asarray(dense(
            tokens=toks, start=start, n_new=n_new,
            **{f"cache_{kv}{i}": np.zeros(
                (1, t, cfg.n_kv_heads, cfg.d_head), np.float32)
               for kv in "kv" for i in range(cfg.n_layers)})[0])[0])
        feeds = {"tokens": toks, "start": start, "n_new": n_new,
                 "block_tables": np.asarray([[3, 1]], np.int32)}
        for kv in "kv":
            for i in range(cfg.n_layers):
                feeds[f"cache_{kv}{i}"] = np.zeros(
                    (n_blocks, page, cfg.n_kv_heads * cfg.d_head), np.int8)
                feeds[f"cache_{kv}{i}_scale"] = np.zeros(
                    (n_blocks, cfg.n_kv_heads), np.float32)
        got.append(np.asarray(kv8(**feeds)[0])[0])
    want, got = np.stack(want), np.stack(got)
    assert want.shape == got.shape == (t, cfg.vocab)
    assert np.abs(got - want).max() < 0.05, np.abs(got - want).max()
    np.testing.assert_array_equal(got.argmax(-1), want.argmax(-1))


# --------------------------------------------------------------------------- #
# engine end-to-end: kv8 paged vs the fp32 dense reference
# --------------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def kv8_engine():
    # the shared paged-int8 matrix variant (conftest.ENGINE_VARIANTS)
    return make_engine("paged-int8")


def _exact(engine, ref, reqs):
    for r in reqs:
        assert engine.submit(r), r.dropped
    engine.run(max_ticks=engine.tick + 4000)
    for r in reqs:
        assert r.done and r.dropped is None, (r.uid, r.dropped)
        want = ref.generate(r.prompt, r.max_new_tokens)
        assert r.out_tokens == want, (r.uid, r.out_tokens, want)
    engine.sched.check_conservation()
    engine.stepper.pool.check_integrity()


def test_kv8_engine_token_exact_cold(kv8_engine):
    engine, ref = kv8_engine
    assert engine.stepper.pool.kv_dtype == "int8"
    rng = np.random.default_rng(21)
    reqs = [EngineRequest(
        uid=i, prompt=rng.integers(0, TINY.vocab,
                                   size=int(rng.integers(1, 13)))
        .astype(np.int32),
        max_new_tokens=int(rng.integers(1, 7))) for i in range(7)]
    _exact(engine, ref, reqs)
    assert engine.stepper.pool.stats()["live_blocks"] == 0


def test_kv8_engine_prefix_hit_exact(kv8_engine):
    engine, ref = kv8_engine
    rng = np.random.default_rng(22)
    prefix = rng.integers(0, TINY.vocab, size=24).astype(np.int32)
    cold = EngineRequest(uid=100, prompt=np.concatenate(
        [prefix, rng.integers(0, TINY.vocab, size=3).astype(np.int32)]),
        max_new_tokens=5)
    _exact(engine, ref, [cold])
    hits0 = engine.stepper.pool.hit_tokens
    warm = EngineRequest(uid=101, prompt=np.concatenate(
        [prefix, rng.integers(0, TINY.vocab, size=2).astype(np.int32)]),
        max_new_tokens=5)
    _exact(engine, ref, [warm])
    assert engine.stepper.pool.hit_tokens - hits0 >= 24, \
        "quantized pages never prefix-hit"


def test_kv8_engine_cow_divergence_exact(kv8_engine):
    """Requests diverging off a shared quantized partial tail page: the
    first write must copy the int8 page AND its scale row (they are one
    unit), and every stream stays token-exact."""
    engine, ref = kv8_engine
    rng = np.random.default_rng(23)
    pre = rng.integers(0, TINY.vocab, size=21).astype(np.int32)
    seed_req = EngineRequest(uid=200, prompt=pre, max_new_tokens=2)
    _exact(engine, ref, [seed_req])
    cow0 = engine.stepper.pool.cow_count
    reqs = [EngineRequest(uid=201 + i, prompt=np.concatenate(
        [pre, rng.integers(0, TINY.vocab, size=2 + i).astype(np.int32)]),
        max_new_tokens=4) for i in range(3)]
    _exact(engine, ref, reqs)
    assert engine.stepper.pool.cow_count > cow0, "CoW never fired"


def test_kv8_composes_with_int8_programs():
    """kv_dtype="int8" (cache pages) and quantize="int8" (weights) are
    orthogonal; together they must still match the fp32 dense-cache
    int8-Program reference token for token."""
    engine, ref = make_engine("paged-int8", n_slots=2, cache_cap=32,
                              quantize="int8")
    rng = np.random.default_rng(24)
    reqs = [EngineRequest(
        uid=i, prompt=rng.integers(0, TINY.vocab,
                                   size=int(rng.integers(1, 11)))
        .astype(np.int32),
        max_new_tokens=int(rng.integers(1, 5))) for i in range(4)]
    _exact(engine, ref, reqs)


# --------------------------------------------------------------------------- #
# byte-honest pool accounting + validation
# --------------------------------------------------------------------------- #

def test_kv_page_bytes_accounts_scale_sidecars():
    # fp32: layers * K,V * rows * heads * dim * 4B
    assert kv_page_bytes(2, 2, 8, 8) == 2 * 2 * 8 * 2 * 8 * 4
    assert kv_page_bytes(1, 4, 16, 8, "bfloat16") == 1 * 2 * 8 * 4 * 16 * 2
    # int8 adds one f32 scale per (layer, K/V, kv-head)
    assert kv_page_bytes(2, 2, 8, 8, "int8") == (2 * 2 * 8 * 2 * 8
                                                 + 2 * 2 * 2 * 4)
    with pytest.raises(ValueError):
        kv_page_bytes(1, 2, 8, 8, "int4")


def test_block_pool_reports_bytes():
    pb = kv_page_bytes(2, 2, 8, 8, "int8")
    pool = BlockPool(4, 8, kv_dtype="int8", page_bytes=pb)
    s = pool.stats()
    assert s["kv_dtype"] == "int8" and s["page_bytes"] == pb
    assert s["pool_bytes"] == 4 * pb and s["live_bytes"] == 0
    # no page_bytes given -> byte fields are honest Nones, not guesses
    s2 = BlockPool(4, 8).stats()
    assert s2["kv_dtype"] == "float32"
    assert s2["page_bytes"] is None and s2["pool_bytes"] is None


def test_kv_dtype_validation_errors():
    with pytest.raises(ValueError):
        BlockPool(4, 8, kv_dtype="int4")
    with pytest.raises(ValueError, match="paged"):
        build_lm_serving(TINY, n_slots=2, chunk=4, cache_cap=32,
                         kv_dtype="int8")          # dense engine: no pages
    params = init_lm_params(TINY, 0)
    with pytest.raises(ValueError):
        build_paged_prefill_graph(TINY, params, batch=1, chunk=4,
                                  n_blocks=4, page_size=8, max_pages=2,
                                  kv_dtype="float16")
