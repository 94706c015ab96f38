"""Per-kernel shape/dtype sweeps: every Pallas kernel (interpret mode)
against its pure-jnp oracle in ref.py."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ref as R
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import flash_decode, flash_decode_partial
from repro.kernels.gemm import batched_gemm, gemm
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd import ssd_scan

rng = np.random.default_rng(7)


def randn(shape, dtype=jnp.float32):
    return jnp.asarray(rng.standard_normal(shape), dtype)


def tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("b,sq,skv,hq,hkv,d,causal,window", [
    (2, 128, 128, 4, 2, 64, True, None),
    (1, 256, 256, 8, 8, 128, True, None),
    (2, 128, 256, 4, 1, 64, True, None),      # chunked-prefill offset
    (1, 256, 256, 4, 2, 64, True, 64),        # sliding window
    (1, 128, 128, 2, 2, 96, False, None),     # non-causal, odd head dim
    (1, 64, 64, 2, 1, 32, True, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_vs_ref(b, sq, skv, hq, hkv, d, causal, window, dtype):
    q, k, v = (randn((b, sq, hq, d), dtype), randn((b, skv, hkv, d), dtype),
               randn((b, skv, hkv, d), dtype))
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_kv=64, interpret=True)
    ref = R.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol(dtype))


def test_flash_attention_mixed_v_dim():
    """MLA shape: v head dim != qk head dim."""
    q, k = randn((1, 128, 4, 96)), randn((1, 128, 2, 96))
    v = randn((1, 128, 2, 64))
    out = flash_attention(q, k, v, block_q=64, block_kv=64, interpret=True)
    ref = R.attention_ref(q, k, v)
    assert out.shape == (1, 128, 4, 64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("b,skv,hq,hkv,d", [
    (2, 256, 8, 2, 64), (1, 512, 4, 1, 128), (3, 128, 16, 16, 96),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_vs_ref(b, skv, hq, hkv, d, dtype):
    q = randn((b, hq, d), dtype)
    k, v = randn((b, skv, hkv, d), dtype), randn((b, skv, hkv, d), dtype)
    lens = jnp.asarray(rng.integers(1, skv + 1, (b,)), jnp.int32)
    out = flash_decode(q, k, v, lens, block_kv=64, interpret=True)
    ref = R.decode_attention_ref(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol(dtype))


def test_flash_decode_partial_combine_exact():
    """Sharded partials combined == full softmax (the tree-decode identity),
    including an all-masked shard (length 0)."""
    b, skv, hq, hkv, d = 2, 256, 8, 2, 64
    q, k, v = randn((b, hq, d)), randn((b, skv, hkv, d)), randn((b, skv, hkv, d))
    lens = jnp.asarray([100, 256], jnp.int32)
    ref = R.decode_attention_ref(q, k, v, lens)
    parts = []
    n_shards = 4
    per = skv // n_shards
    for i in range(n_shards):
        local_len = jnp.clip(lens - i * per, 0, per)
        parts.append(flash_decode_partial(q, k[:, i*per:(i+1)*per],
                                          v[:, i*per:(i+1)*per], local_len,
                                          block_kv=64, interpret=True))
    outs = jnp.stack([p[0] for p in parts])
    ms = jnp.stack([p[1] for p in parts])
    ls = jnp.stack([p[2] for p in parts])
    comb = R.combine_partials_ref(outs, ms, ls)
    np.testing.assert_allclose(np.asarray(comb), np.asarray(ref), atol=2e-5)


# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("b,s,h,p,g,n,chunk", [
    (2, 128, 4, 64, 1, 32, 32),
    (1, 256, 8, 64, 2, 128, 64),
    (2, 64, 2, 32, 2, 16, 64),      # chunk > seq
    (1, 96, 4, 32, 1, 64, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_kernel_vs_ref(b, s, h, p, g, n, chunk, dtype):
    x = randn((b, s, h, p), dtype)
    dt = jnp.asarray(np.abs(rng.standard_normal((b, s, h))) * 0.1 + 0.01,
                     jnp.float32)
    A = jnp.asarray(-np.abs(rng.standard_normal((h,))) - 0.1, jnp.float32)
    B = randn((b, s, g, n), dtype)
    C = randn((b, s, g, n), dtype)
    D = jnp.asarray(rng.standard_normal((h,)), jnp.float32)
    y_ref, st_ref = R.ssd_ref(x, dt, A, B, C, D)
    y_k, st_k = ssd_scan(x, dt, A, B, C, D, chunk=min(chunk, s), interpret=True)
    # bf16 outputs round at ~0.4% ULP on O(10) magnitudes: compare relative
    atol = 5e-4 if dtype == jnp.float32 else 5e-2
    rtol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y_k, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=rtol, atol=atol)
    # the carried state is f32 in both implementations: tight tolerance
    np.testing.assert_allclose(np.asarray(st_k), np.asarray(st_ref), atol=5e-4)


def test_ssd_chunked_matches_sequential():
    b, s, h, p, g, n = 1, 128, 2, 32, 1, 16
    x = randn((b, s, h, p))
    dt = jnp.asarray(np.abs(rng.standard_normal((b, s, h))) * 0.1 + 0.01)
    A = jnp.asarray(-np.abs(rng.standard_normal((h,))) - 0.1)
    B, C = randn((b, s, g, n)), randn((b, s, g, n))
    y1, s1 = R.ssd_ref(x, dt, A, B, C, None)
    y2, s2 = R.ssd_chunked_ref(x, dt, A, B, C, None, chunk=32)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-4)


def test_ssd_step_continues_prefill():
    """decode steps continuing a prefill state == one long scan."""
    b, s, h, p, g, n = 1, 64, 2, 16, 1, 8
    x = randn((b, s + 4, h, p))
    dt = jnp.asarray(np.abs(rng.standard_normal((b, s + 4, h))) * 0.1 + 0.01)
    A = jnp.asarray(-np.abs(rng.standard_normal((h,))) - 0.1)
    B, C = randn((b, s + 4, g, n)), randn((b, s + 4, g, n))
    D = jnp.asarray(rng.standard_normal((h,)))
    y_full, _ = R.ssd_ref(x, dt, A, B, C, D)
    _, state = R.ssd_ref(x[:, :s], dt[:, :s], A, B[:, :s], C[:, :s], D)
    for t in range(s, s + 4):
        y_t, state = R.ssd_step_ref(x[:, t], dt[:, t], A, B[:, t], C[:, t],
                                    D, state)
        np.testing.assert_allclose(np.asarray(y_t), np.asarray(y_full[:, t]),
                                   atol=1e-4)


# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("shape", [(4, 17, 256), (2, 100, 1024), (384, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_vs_ref(shape, dtype):
    x = randn(shape, dtype)
    w = randn(shape[-1:], dtype)
    r = randn(shape, dtype)
    out = rmsnorm(x, w, block_rows=64, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(R.rmsnorm_ref(x, w), np.float32),
                               atol=tol(dtype))
    out_r = rmsnorm(x, w, residual=r, block_rows=64, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out_r, np.float32),
        np.asarray(R.rmsnorm_ref(x, w, residual=r), np.float32),
        atol=tol(dtype))


# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("m,k,n", [(256, 512, 256), (100, 300, 200),
                                   (33, 65, 129), (1, 128, 1)])
def test_gemm_vs_ref(m, k, n):
    x, w = randn((m, k)), randn((k, n))
    out = gemm(x, w, block_m=64, block_n=64, block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(R.gemm_ref(x, w)),
                               atol=1e-3)


@pytest.mark.parametrize("e,m,k,n", [(4, 64, 128, 96), (3, 50, 70, 30)])
def test_batched_gemm_vs_ref(e, m, k, n):
    x, w = randn((e, m, k)), randn((e, k, n))
    out = batched_gemm(x, w, block_m=32, block_n=32, block_k=64,
                       interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(R.batched_gemm_ref(x, w)), atol=1e-3)


def test_gemm_bf16_accumulates_f32():
    x = randn((128, 256), jnp.bfloat16)
    w = randn((256, 128), jnp.bfloat16)
    out = gemm(x, w, interpret=True)
    ref = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=2e-2, atol=2e-1)


# --------------------------------------------------------------------------- #
# SMEM operand layouts: per-row lengths/starts are whole arrays indexed by the
# grid row, and int8 scale sidecars are whole arrays indexed through the block
# table.  Every row gets a different length/start and every (page, head) tile
# a different scale, so reading any other entry than its own breaks parity.
# --------------------------------------------------------------------------- #

from repro.kernels.flash_attention import (flash_chunk_attention,  # noqa: E402
                                           flash_paged_chunk_attention)
from repro.kernels.flash_decode import flash_paged_decode  # noqa: E402
from repro.kernels.serving_ops import chunk_attention  # noqa: E402


@pytest.mark.parametrize("lengths", [(1, 23, 64, 40), (64, 40, 23, 1)])
def test_flash_decode_lengths_indexed_by_grid_row(lengths):
    q, k, v = randn((4, 4, 32)), randn((4, 64, 2, 32)), randn((4, 64, 2, 32))
    lens = jnp.asarray(lengths, jnp.int32)
    out = flash_decode(q, k, v, lens, block_kv=16, interpret=True)
    ref = R.decode_attention_ref(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("starts", [(0, 9, 56, 17), (56, 17, 0, 9)])
def test_flash_chunk_attention_starts_indexed_by_grid_row(starts):
    q, k, v = randn((4, 8, 4, 32)), randn((4, 64, 2, 32)), randn((4, 64, 2, 32))
    start = jnp.asarray(starts, jnp.int32)
    out = flash_chunk_attention(q, k, v, start, block_q=8, block_kv=16,
                                interpret=True)
    ref = chunk_attention(q, k, v, start, backend="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def _int8_pool(n_blocks, page, hk, d):
    """Flat (N, P, Hk*D) int8 pages with a distinct scale for every
    (page, kv head) tile."""
    shape = (n_blocks, page, hk * d)
    pk = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
    pv = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
    n = n_blocks * hk
    # dequantized values stay within +-2, every tile on its own scale
    ks, vs = [jnp.asarray((1 + rng.permutation(n)).reshape(n_blocks, hk)
                          * (2.0 / (127 * n)), jnp.float32) for _ in "kv"]
    return pk, ks, pv, vs


def _dequant_gather(pages, scales, tables):
    """(B, MP*P, H, D) fp32 view of each sequence's flat int8 pages."""
    n, p, hkd = pages.shape
    hk = scales.shape[1]
    heads = pages.reshape(n, p, hk, hkd // hk)
    g = jnp.take(heads, tables, axis=0).astype(jnp.float32)
    g = g * jnp.take(scales, tables, axis=0)[:, :, None, :, None]
    return g.reshape(tables.shape[0], -1, hk, hkd // hk)


@pytest.mark.parametrize("hq,hk", [(4, 2), (4, 4)])
def test_flash_paged_decode_int8_sidecar_rows(hq, hk):
    n_blocks, page, d, b, mp = 12, 8, 32, 3, 4
    pk, ks, pv, vs = _int8_pool(n_blocks, page, hk, d)
    tables = jnp.asarray(rng.permutation(n_blocks)[:b * mp].reshape(b, mp),
                         jnp.int32)
    lens = jnp.asarray([5, 32, 19], jnp.int32)
    q = randn((b, hq, d))
    out = flash_paged_decode(q, pk, pv, tables, lens, k_scales=ks,
                             v_scales=vs, interpret=True)
    ref = R.decode_attention_ref(q, _dequant_gather(pk, ks, tables),
                                 _dequant_gather(pv, vs, tables), lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5,
                               atol=1e-4)


@pytest.mark.parametrize("hq,hk", [(4, 2), (4, 4)])
def test_flash_paged_chunk_int8_sidecar_rows(hq, hk):
    n_blocks, page, d, b, mp, t = 12, 8, 32, 3, 4, 8
    pk, ks, pv, vs = _int8_pool(n_blocks, page, hk, d)
    tables = jnp.asarray(rng.permutation(n_blocks)[:b * mp].reshape(b, mp),
                         jnp.int32)
    start = jnp.asarray([0, 24, 11], jnp.int32)
    q = randn((b, t, hq, d))
    out = flash_paged_chunk_attention(q, pk, pv, tables, start, k_scales=ks,
                                      v_scales=vs, interpret=True)
    ref = chunk_attention(q, _dequant_gather(pk, ks, tables),
                          _dequant_gather(pv, vs, tables), start,
                          backend="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5,
                               atol=1e-4)
