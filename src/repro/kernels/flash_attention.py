"""Flash attention (prefill/training-forward) as a Pallas TPU kernel.

Blockwise-softmax attention tiled for VMEM: the query block, one KV block,
and the f32 accumulator live in VMEM; the (Sq x Skv) score matrix is never
materialised in HBM.  Grid = (batch*q_heads, Sq/bq, Skv/bkv) with the KV
axis innermost — TPU grid iteration is sequential, so the running max /
sum-of-exp / accumulator scratch carries across KV blocks of one query block
(the classic online-softmax recurrence).

GQA is handled by index maps (each q head reads its kv head h // group);
KV is never materialised repeated.  Causal and sliding-window masks skip
fully-masked KV blocks with ``pl.when`` (no MXU work issued for them).

Block sizes default to (bq, bkv) = (256, 512), clamped to the sequence
lengths; head_dim is used as-is (Mosaic pads the lane dim to 128 — full MXU
efficiency needs D % 128 == 0, true for 7/10 assigned archs; see DESIGN.md).

VMEM budget at defaults, D=128, f32 scratch: q 256x128x4 + kv 2x512x128x4
+ acc 256x128x4 + m/l 2x256x128x4 ≈ 1.0 MB — comfortably inside 16 MB, and
Pallas double-buffers the KV streams automatically.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

__all__ = ["flash_attention", "flash_chunk_attention",
           "flash_paged_chunk_attention"]

_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: Optional[int],
                  bq: int, bkv: int, n_kv_blocks: int, q_offset: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # absolute positions of this block's first row/col
    row0 = qi * bq + q_offset
    col0 = ki * bkv
    # any (row, col) pair live in this block?
    live = jnp.bool_(True)
    if causal:
        live &= (row0 + bq - 1) >= col0          # max row reaches min col
    if window is not None:
        live &= (col0 + bkv - 1) > (row0 - window)  # max col inside min row's window

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, D)
        k = k_ref[0].astype(jnp.float32)                  # (bkv, D)
        v = v_ref[0].astype(jnp.float32)                  # (bkv, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (bq,bkv)
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = jnp.ones((bq, bkv), dtype=jnp.bool_)
        if causal:
            mask &= cols <= rows
        if window is not None:
            mask &= cols > rows - window
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:, :1]                              # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                             # (bq, bkv)
        l_ref[:, :1] = l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[:, :1] = m_new

    @pl.when(ki == n_kv_blocks - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    scale: Optional[float] = None,
                    block_q: int = 256, block_kv: int = 512,
                    interpret: bool = False) -> jax.Array:
    """q (B, Sq, Hq, D), k/v (B, Skv, Hkv, D) -> (B, Sq, Hq, D).

    Query row i sits at absolute position (Skv - Sq + i), matching
    ``ref.attention_ref`` (relevant for chunked prefill where Sq < Skv).
    """
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    dv = v.shape[3]  # may differ from d (MLA: qk_dim 192 vs v_dim 128)
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    bq = min(block_q, sq)
    bkv = min(block_kv, skv)
    assert sq % bq == 0 and skv % bkv == 0, (
        f"seq lengths must divide block sizes: {sq}%{bq}, {skv}%{bkv}")
    nq, nkv = sq // bq, skv // bkv

    # (B, S, H, D) -> (B*H, S, D): head-major layout keeps index maps trivial.
    qr = q.transpose(0, 2, 1, 3).reshape(b * hq, sq, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * hkv, skv, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * hkv, skv, dv)

    def q_map(bh, qi, ki):
        return (bh, qi, 0)

    def kv_map(bh, qi, ki):
        return ((bh // hq) * hkv + (bh % hq) // group, ki, 0)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        bq=bq, bkv=bkv, n_kv_blocks=nkv, q_offset=skv - sq)

    out = pl.pallas_call(
        kernel,
        grid=(b * hq, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, bq, d), q_map),
            pl.BlockSpec((1, bkv, d), kv_map),
            pl.BlockSpec((1, bkv, dv), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, dv), q_map),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, dv), jnp.float32),   # acc
            pltpu.VMEM((bq, 128), jnp.float32),  # running max (col 0 used)
            pltpu.VMEM((bq, 128), jnp.float32),  # running sum-of-exp
        ],
        interpret=interpret,
        name="flash_attention",
    )(qr, kr, vr)
    return out.reshape(b, hq, sq, dv).transpose(0, 2, 1, 3)


def _chunk_flash_kernel(start_ref, q_ref, k_ref, v_ref, o_ref,
                        acc_ref, m_ref, l_ref, *,
                        scale: float, bq: int, bkv: int, n_kv_blocks: int,
                        k_scale=None, v_scale=None):
    """Same online-softmax recurrence as :func:`_flash_kernel`, with the
    query offset a per-sequence runtime value: query row t of the chunk
    sits at absolute position ``start + t`` and attends cache columns
    ``<= start + t`` (offset-causal).  The whole (B*Hq,) ``start`` array
    sits in SMEM and grid row bh reads entry bh."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    start = start_ref[pl.program_id(0)]
    row0 = start + qi * bq                      # abs position of block row 0
    col0 = ki * bkv
    # the block is live iff its max row position reaches its min column
    live = (row0 + bq - 1) >= col0

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, D)
        k = k_ref[0].astype(jnp.float32)                  # (bkv, D)
        v = v_ref[0].astype(jnp.float32)
        if k_scale is not None:
            # int8 tiles: dequantize in-register with the per-(page, head)
            # scalars read from SMEM — no fp32 cache copy exists
            k = k * k_scale
            v = v * v_scale
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (bq,bkv)
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        s = jnp.where(cols <= rows, s, _NEG_INF)

        m_prev = m_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[:, :1] = l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[:, :1] = m_new

    @pl.when(ki == n_kv_blocks - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_chunk_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                          start: jax.Array, *,
                          scale: Optional[float] = None,
                          block_q: int = 256, block_kv: int = 512,
                          interpret: bool = False) -> jax.Array:
    """Chunked-prefill flash attention against a KV cache.

    q (B, T, Hq, D), k/v (B, S, Hk, D), start (B,) int32 -> (B, T, Hq, D).
    Query row t of sequence b is at absolute position ``start[b] + t`` and
    attends cache keys at positions ``<= start[b] + t`` — the contract of
    the ``chunk_attention`` serving op.  Every query sees at least column
    0 (start >= 0), so the softmax is never empty.
    """
    b, t, hq, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    dv = v.shape[3]
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    bq = min(block_q, t)
    bkv = min(block_kv, s)
    assert t % bq == 0 and s % bkv == 0, (
        f"chunk/cache lengths must divide block sizes: {t}%{bq}, {s}%{bkv}")
    nq, nkv = t // bq, s // bkv

    qr = q.transpose(0, 2, 1, 3).reshape(b * hq, t, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * hkv, s, dv)
    start_r = jnp.repeat(start.astype(jnp.int32), hq)        # (B*Hq,)

    def q_map(bh, qi, ki):
        return (bh, qi, 0)

    def kv_map(bh, qi, ki):
        return ((bh // hq) * hkv + (bh % hq) // group, ki, 0)

    kernel = functools.partial(_chunk_flash_kernel, scale=scale,
                               bq=bq, bkv=bkv, n_kv_blocks=nkv)

    out = pl.pallas_call(
        kernel,
        grid=(b * hq, nq, nkv),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),    # whole (B*Hq,) start
            pl.BlockSpec((1, bq, d), q_map),
            pl.BlockSpec((1, bkv, d), kv_map),
            pl.BlockSpec((1, bkv, dv), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, dv), q_map),
        out_shape=jax.ShapeDtypeStruct((b * hq, t, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, dv), jnp.float32),   # acc
            pltpu.VMEM((bq, 128), jnp.float32),  # running max (col 0 used)
            pltpu.VMEM((bq, 128), jnp.float32),  # running sum-of-exp
        ],
        interpret=interpret,
        name="flash_chunk_attention",
    )(start_r, qr, kr, vr)
    return out.reshape(b, hq, t, dv).transpose(0, 2, 1, 3)


# --------------------------------------------------------------------------- #
# Paged chunk-prefill flash attention — the KV "block" of grid step pi is
# PHYSICAL page block_tables[b, pi], reached via a scalar-prefetched index
# map (same trick as flash_decode.flash_paged_decode); the dense gather
# copy the ref/xla paged backends pay never exists here.  Optional int8
# mode: the per-(page, head) scale sidecars sit whole in SMEM, each grid
# step reads its tile's scalar through the same table lookup, and dequant
# happens in-register inside the online-softmax loop.
# --------------------------------------------------------------------------- #

def _paged_chunk_kernel(bt_ref, start_ref, q_ref, k_ref, v_ref, o_ref,
                        acc_ref, m_ref, l_ref, *,
                        scale: float, bq: int, page: int, n_pages: int):
    del bt_ref                     # consumed by the index maps
    _chunk_flash_kernel(start_ref, q_ref, k_ref, v_ref, o_ref,
                        acc_ref, m_ref, l_ref, scale=scale, bq=bq,
                        bkv=page, n_kv_blocks=n_pages)


def _paged_chunk_q_kernel(bt_ref, start_ref, q_ref, k_ref, ksc_ref, v_ref,
                          vsc_ref, o_ref, acc_ref, m_ref, l_ref, *,
                          scale: float, bq: int, page: int, n_pages: int,
                          hq: int, hkv: int):
    # the (N*Hk,) scale sidecars sit whole in SMEM; read the scalar of the
    # (block, kv head) tile the K/V index map fetched for this grid step
    bh, pi = pl.program_id(0), pl.program_id(2)
    row = bt_ref[bh // hq, pi] * hkv + (bh % hq) // (hq // hkv)
    _chunk_flash_kernel(start_ref, q_ref, k_ref, v_ref, o_ref,
                        acc_ref, m_ref, l_ref, scale=scale, bq=bq,
                        bkv=page, n_kv_blocks=n_pages,
                        k_scale=ksc_ref[row], v_scale=vsc_ref[row])


def flash_paged_chunk_attention(q: jax.Array, pages_k: jax.Array,
                                pages_v: jax.Array, block_tables: jax.Array,
                                start: jax.Array, *,
                                k_scales: Optional[jax.Array] = None,
                                v_scales: Optional[jax.Array] = None,
                                scale: Optional[float] = None,
                                block_q: int = 256,
                                interpret: bool = False) -> jax.Array:
    """Chunked-prefill flash attention reading K/V through block tables.

    q (B, T, Hq, D), pages_k/v (N, P, Hk, D), block_tables (B, MP) int32,
    start (B,) int32 -> (B, T, Hq, D).  Query row t of sequence b sits at
    absolute position ``start[b] + t`` and attends cache positions
    ``<= start[b] + t`` — the ``paged_chunk_attention`` op contract.
    Offset-causal masking covers garbage table entries: logical pages past
    the chunk's frontier are wholly masked, so they may hold any valid
    block id.

    With ``k_scales``/``v_scales`` ((N, Hk) float32) the pages are int8
    and each (page, head) tile is dequantized in-register."""
    b, t, hq, d = q.shape
    n_blocks, page, hkv = pages_k.shape[0], pages_k.shape[1], pages_k.shape[2]
    dv = pages_v.shape[3]
    n_pages = block_tables.shape[1]
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    quant = k_scales is not None
    assert quant == (v_scales is not None), "need both k_scales and v_scales"
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    bq = min(block_q, t)
    assert t % bq == 0, f"chunk length must divide block_q: {t} % {bq}"
    nq = t // bq

    qr = q.transpose(0, 2, 1, 3).reshape(b * hq, t, d)
    # pages: (N, P, Hk, D) -> head-major (N*Hk, P, D) so one (block, head)
    # pair is a contiguous (P, D) tile the index map can address directly
    kr = pages_k.transpose(0, 2, 1, 3).reshape(n_blocks * hkv, page, d)
    vr = pages_v.transpose(0, 2, 1, 3).reshape(n_blocks * hkv, page, dv)
    start_r = jnp.repeat(start.astype(jnp.int32), hq)           # (B*Hq,)
    tables = jnp.clip(block_tables, 0, n_blocks - 1).astype(jnp.int32)

    def q_map(bh, qi, pi, bt):
        return (bh, qi, 0)

    def kv_map(bh, qi, pi, bt):
        # physical (block, head) row: sequence bh//Hq, kv head of q head
        return (bt[bh // hq, pi] * hkv + (bh % hq) // group, 0, 0)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)   # whole array in SMEM
    in_specs = [
        smem,                                       # (B*Hq,) start
        pl.BlockSpec((1, bq, d), q_map),
        pl.BlockSpec((1, page, d), kv_map),
        pl.BlockSpec((1, page, dv), kv_map),
    ]
    operands = [start_r, qr, kr, vr]
    kw = {}
    if quant:
        # (N*Hk,) float32 sidecars, 8*N*Hk bytes of SMEM for the pair
        in_specs = in_specs[:3] + [smem, in_specs[3], smem]
        operands = [start_r, qr, kr,
                    jnp.asarray(k_scales, jnp.float32).reshape(-1),
                    vr, jnp.asarray(v_scales, jnp.float32).reshape(-1)]
        body = _paged_chunk_q_kernel
        kw = {"hq": hq, "hkv": hkv}
    else:
        body = _paged_chunk_kernel

    kernel = functools.partial(body, scale=scale, bq=bq, page=page,
                               n_pages=n_pages, **kw)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                     # the block table
        grid=(b * hq, nq, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bq, dv), q_map),
        scratch_shapes=[
            pltpu.VMEM((bq, dv), jnp.float32),   # acc
            pltpu.VMEM((bq, 128), jnp.float32),  # running max (col 0 used)
            pltpu.VMEM((bq, 128), jnp.float32),  # running sum-of-exp
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hq, t, dv), q.dtype),
        interpret=interpret,
        name=("flash_paged_chunk_attention_q" if quant
              else "flash_paged_chunk_attention"),
    )(tables, *operands)
    return out.reshape(b, hq, t, dv).transpose(0, 2, 1, 3)
