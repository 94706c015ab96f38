"""Flash-decode: single-new-token attention against a long KV cache.

Decode attention is memory-bound (read the whole KV cache per step, O(1)
compute per byte), so the kernel's job is purely streaming: iterate KV
blocks through VMEM, maintain the online-softmax state, touch each cache
byte exactly once.

TPU adaptation of GPU "flash decoding":

* One grid row handles a whole **GQA group** — the ``group = Hq/Hkv`` query
  heads that share a kv head form the (group, D) q block, so the KV stream
  is read once per kv head, not once per q head, and the q rows give the
  MXU/VPU some sublane parallelism (group is 1..32 across our archs).
* Grid = (B * Hkv, Skv/bkv), KV axis innermost and sequential; acc/m/l
  scratch carries across KV blocks.
* Variable cache lengths are masked via a per-(batch, kv head) length
  operand: the whole (B*Hkv,) int32 array sits in SMEM and grid row bh
  reads entry bh (Mosaic refuses rank-1 SMEM blocks smaller than 128).
* The kernel also emits its running (m, l) so callers can combine partials
  across devices — this is the building block of the sequence-parallel
  "tree decode" in ``repro/sharding/collectives.py`` (KV cache sharded over
  the data axis for long_500k; partials merged with a cheap psum).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

__all__ = ["flash_decode", "flash_decode_partial", "flash_paged_decode"]

_NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_out_ref, l_out_ref,
                   acc_ref, m_ref, l_ref, *,
                   scale: float, bkv: int, n_kv_blocks: int, emit_stats: bool,
                   k_scale=None, v_scale=None):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[pl.program_id(0)]
    live = (ki * bkv) < length  # block has at least one valid entry

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale        # (group, D)
        k = k_ref[0].astype(jnp.float32)                # (bkv, D)
        v = v_ref[0].astype(jnp.float32)
        if k_scale is not None:
            # int8 tiles: dequantize in-register with the per-(page, head)
            # scalars read from SMEM — no fp32 cache copy exists
            k = k * k_scale
            v = v * v_scale
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (group,bkv)
        cols = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < length, s, _NEG_INF)

        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[:, :1] = l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[:, :1] = m_new

    @pl.when(ki == n_kv_blocks - 1)
    def _finish():
        if emit_stats:
            # unnormalised partials: caller combines across KV shards
            o_ref[0] = acc_ref[...].astype(o_ref.dtype)
            m_out_ref[0] = m_ref[:, :1].astype(m_out_ref.dtype)
            l_out_ref[0] = l_ref[:, :1].astype(l_out_ref.dtype)
        else:
            l = jnp.maximum(l_ref[:, :1], 1e-30)
            o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
            m_out_ref[0] = m_ref[:, :1].astype(m_out_ref.dtype)
            l_out_ref[0] = l_ref[:, :1].astype(l_out_ref.dtype)


def _flash_decode(q, k, v, lengths, scale, block_kv, interpret, emit_stats):
    b, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    dv = v.shape[3]  # may differ from d (MLA absorbed decode: 576 vs 512)
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    bkv = min(block_kv, skv)
    assert skv % bkv == 0, (skv, bkv)
    nkv = skv // bkv

    if lengths is None:
        lengths = jnp.full((b,), skv, jnp.int32)
    # q: (B, Hq, D) -> (B*Hkv, group, D); kv: (B, Skv, Hkv, D) -> (B*Hkv, Skv, D)
    qr = q.reshape(b, hkv, group, d).reshape(b * hkv, group, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * hkv, skv, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * hkv, skv, dv)
    len_r = jnp.repeat(lengths.astype(jnp.int32), hkv)  # (B*Hkv,)

    kernel = functools.partial(_decode_kernel, scale=scale, bkv=bkv,
                               n_kv_blocks=nkv, emit_stats=emit_stats)
    grid = (b * hkv, nkv)
    out, m, l = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),    # whole (B*Hkv,) lengths
            pl.BlockSpec((1, group, d), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, bkv, d), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, bkv, dv), lambda bh, ki: (bh, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, group, dv), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, group, 1), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, group, 1), lambda bh, ki: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * hkv, group, dv), q.dtype),
            jax.ShapeDtypeStruct((b * hkv, group, 1), jnp.float32),
            jax.ShapeDtypeStruct((b * hkv, group, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((group, dv), jnp.float32),
            pltpu.VMEM((group, 128), jnp.float32),
            pltpu.VMEM((group, 128), jnp.float32),
        ],
        interpret=interpret,
        name="flash_decode",
    )(len_r, qr, kr, vr)
    out = out.reshape(b, hq, dv)
    m = m.reshape(b, hq)
    l = l.reshape(b, hq)
    return out, m, l


def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 lengths: Optional[jax.Array] = None, *,
                 scale: Optional[float] = None, block_kv: int = 512,
                 interpret: bool = False) -> jax.Array:
    """q (B, Hq, D), k/v (B, Skv, Hkv, D) -> (B, Hq, D), softmax-normalised."""
    out, _, _ = _flash_decode(q, k, v, lengths, scale, block_kv, interpret,
                              emit_stats=False)
    return out


def flash_decode_partial(q: jax.Array, k: jax.Array, v: jax.Array,
                         lengths: Optional[jax.Array] = None, *,
                         scale: Optional[float] = None, block_kv: int = 512,
                         interpret: bool = False
                         ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Unnormalised flash partials (acc, m, l) over THIS device's KV shard —
    combine shards with ``ref.combine_partials_ref`` (exact)."""
    return _flash_decode(q, k, v, lengths, scale, block_kv, interpret,
                         emit_stats=True)


# --------------------------------------------------------------------------- #
# Paged flash decode — KV pages reached through a scalar-prefetched block
# table.  Same streaming recurrence as _decode_kernel, but the KV "block"
# of grid step pi is PHYSICAL page block_tables[b, pi]: the index map reads
# the prefetched table, so pages are DMA'd straight from wherever they live
# in the pool — the dense gather copy the ref/xla paged backends pay never
# exists here.  Garbage table entries (logical pages past a sequence's
# length, filled with 0 by the engine) are masked by the per-sequence
# length operand exactly like short caches in plain flash_decode.
# --------------------------------------------------------------------------- #

def _paged_decode_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref,
                         o_ref, m_out_ref, l_out_ref, acc_ref, m_ref, l_ref,
                         *, scale: float, page: int, n_pages: int):
    # bt_ref (the scalar-prefetched block table) is consumed by the index
    # maps; the compute body is the stock online-softmax recurrence with
    # one page per KV step.
    del bt_ref
    _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_out_ref, l_out_ref,
                   acc_ref, m_ref, l_ref, scale=scale, bkv=page,
                   n_kv_blocks=n_pages, emit_stats=False)


def _paged_decode_q_kernel(bt_ref, len_ref, q_ref, k_ref, ksc_ref, v_ref,
                           vsc_ref, o_ref, m_out_ref, l_out_ref,
                           acc_ref, m_ref, l_ref,
                           *, scale: float, page: int, n_pages: int, hkv: int):
    # int8 variant: the whole flattened (N*Hkv,) scale sidecars sit in
    # SMEM and each grid step reads the scalar of the (block, head) tile
    # its K/V index map fetched — same table lookup, done here by hand;
    # dequant happens inside _decode_kernel's compute body.
    bh, pi = pl.program_id(0), pl.program_id(1)
    row = bt_ref[bh // hkv, pi] * hkv + bh % hkv
    _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_out_ref, l_out_ref,
                   acc_ref, m_ref, l_ref, scale=scale, bkv=page,
                   n_kv_blocks=n_pages, emit_stats=False,
                   k_scale=ksc_ref[row], v_scale=vsc_ref[row])


def flash_paged_decode(q: jax.Array, pages_k: jax.Array, pages_v: jax.Array,
                       block_tables: jax.Array,
                       lengths: Optional[jax.Array] = None, *,
                       k_scales: Optional[jax.Array] = None,
                       v_scales: Optional[jax.Array] = None,
                       scale: Optional[float] = None,
                       interpret: bool = False) -> jax.Array:
    """q (B, Hq, D), pages_k/v (N, P, Hkv*D), block_tables (B, MP) int32,
    lengths (B,) -> (B, Hq, D), softmax-normalised.

    Logical position ``pi * P + r`` of sequence b lives at physical row
    ``(pages[block_tables[b, pi]], r)``; positions >= lengths[b] are
    masked (so unallocated table entries may hold any valid block id).

    With ``k_scales``/``v_scales`` ((N, Hkv) float32) the pages are int8
    and each (page, head) tile is dequantized in-register — the int8
    bytes are all that ever stream through VMEM."""
    b, hq, d = q.shape
    n_blocks, page = pages_k.shape[0], pages_k.shape[1]
    hkv = pages_k.shape[2] // d
    dv = pages_v.shape[2] // hkv
    n_pages = block_tables.shape[1]
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    quant = k_scales is not None
    assert quant == (v_scales is not None), "need both k_scales and v_scales"
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    if lengths is None:
        lengths = jnp.full((b,), n_pages * page, jnp.int32)

    # q: (B, Hq, D) -> (B*Hkv, group, D); pages: (N, P, Hkv*D) -> head-major
    # (N*Hkv, P, D) so one (block, head) pair is a contiguous (P, D) tile.
    qr = q.reshape(b, hkv, group, d).reshape(b * hkv, group, d)
    kr = (pages_k.reshape(n_blocks, page, hkv, d).transpose(0, 2, 1, 3)
          .reshape(n_blocks * hkv, page, d))
    vr = (pages_v.reshape(n_blocks, page, hkv, dv).transpose(0, 2, 1, 3)
          .reshape(n_blocks * hkv, page, dv))
    len_r = jnp.repeat(lengths.astype(jnp.int32), hkv)          # (B*Hkv,)
    tables = jnp.clip(block_tables, 0, n_blocks - 1).astype(jnp.int32)

    def kv_map(bh, pi, bt):
        # physical (block, head) row of logical page pi of sequence bh//Hkv
        return (bt[bh // hkv, pi] * hkv + bh % hkv, 0, 0)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)   # whole array in SMEM
    in_specs = [
        smem,                                       # (B*Hkv,) lengths
        pl.BlockSpec((1, group, d), lambda bh, pi, bt: (bh, 0, 0)),
        pl.BlockSpec((1, page, d), kv_map),
        pl.BlockSpec((1, page, dv), kv_map),
    ]
    operands = [len_r, qr, kr, vr]
    kw = {}
    if quant:
        # (N*Hkv,) float32 sidecars, 8*N*Hkv bytes of SMEM for the pair
        in_specs = in_specs[:3] + [smem, in_specs[3], smem]
        operands = [len_r, qr, kr,
                    jnp.asarray(k_scales, jnp.float32).reshape(-1),
                    vr, jnp.asarray(v_scales, jnp.float32).reshape(-1)]
        body = _paged_decode_q_kernel
        kw = {"hkv": hkv}
    else:
        body = _paged_decode_kernel

    kernel = functools.partial(body, scale=scale, page=page,
                               n_pages=n_pages, **kw)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                     # the block table
        grid=(b * hkv, n_pages),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, group, dv), lambda bh, pi, bt: (bh, 0, 0)),
            pl.BlockSpec((1, group, 1), lambda bh, pi, bt: (bh, 0, 0)),
            pl.BlockSpec((1, group, 1), lambda bh, pi, bt: (bh, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((group, dv), jnp.float32),
            pltpu.VMEM((group, 128), jnp.float32),
            pltpu.VMEM((group, 128), jnp.float32),
        ],
    )
    out, _, _ = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b * hkv, group, dv), q.dtype),
            jax.ShapeDtypeStruct((b * hkv, group, 1), jnp.float32),
            jax.ShapeDtypeStruct((b * hkv, group, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_paged_decode_q" if quant else "flash_paged_decode",
    )(tables, *operands)
    return out.reshape(b, hq, dv)
