"""Serving graph ops — the pieces that let a decoder LM's prefill and
decode steps be expressed as GraphIR and compiled into
:class:`~repro.core.program.Program` artifacts (the serving engine's step
functions in :mod:`repro.runtime.engine`).

Three ops, each with an explicit functional-state contract (caches are
graph inputs AND outputs, so a Program stays a pure function):

* ``embedding``       — token id -> row lookup.
* ``cache_update``    — length-aware scatter of new K/V rows into a
  fixed-capacity cache at per-sequence offsets.  ``n_new`` rows are
  written starting at ``start``; slots with ``n_new == 0`` are untouched,
  which is how one fixed-batch Program serves a mix of active and idle
  slots.
* ``chunk_attention`` — chunked-prefill attention: a chunk of T queries
  at absolute positions ``start .. start+T-1`` attends to cache keys at
  positions ``<= start + t`` (offset-causal).  With T=1 this degenerates
  to single-token decode; the decode graph instead uses the existing
  ``decode_attention`` op so the flash-decode Pallas backend stays
  selectable on the hot path.

Each op carries *multiple* backends — that is the point of running the
serving hot path through the registry at all: the selector, the cost
models and the autotuner finally have something to choose from under
sustained traffic.

* ``ref``    — jnp oracle (vmap'd masked gather/scatter, dense fp32
  attention with the GQA heads materialised).
* ``xla``    — fused lowerings: one-hot-matmul embedding (MXU instead of
  gather), per-slot ``dynamic_update_slice`` cache writes, GQA attention
  grouped in the einsum so the repeated K/V expansion is never
  materialised.
* ``pallas`` — flash-style ``chunk_attention`` reusing the online-softmax
  machinery of :mod:`repro.kernels.flash_attention` with per-sequence
  offset-causal masking (``supports()`` guards block divisibility).

``decode_attention`` additionally gains a ``pallas_split`` split-KV
backend (registered in :mod:`repro.kernels.ops`) for long caches.

All shapes are static (fixed batch = engine slots, fixed chunk size,
fixed cache capacity), so each serving step jits exactly once.
"""

from __future__ import annotations

import math
from contextlib import contextmanager
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.ir import TensorSpec
from repro.core.registry import Cost, defop, get_impl, get_op, impl
from repro.kernels import ref as R
from repro.kernels.flash_attention import flash_chunk_attention
from repro.kernels.ops import pallas_interpret

__all__ = ["embedding", "cache_update", "chunk_attention", "greedy_token",
           "moe_ffn", "last_new_row", "serving_mesh",
           "current_serving_mesh"]

Attrs = Dict[str, Any]


def _bytes(specs: Sequence[TensorSpec]) -> float:
    return float(sum(s.nbytes for s in specs))


def _precision(attrs: Attrs):
    """The node's ``precision`` attr ("highest", ...) as a
    ``jax.lax.Precision``; None (the op's default) where it has none."""
    p = attrs.get("precision")
    return None if p is None else jax.lax.Precision(p)


def _no_window(specs, attrs) -> bool:
    """For backends with no attention window: refuse a node that has one."""
    return not attrs.get("window")


# --------------------------------------------------------------------------- #
# Serving mesh context — how the ``tp`` backends learn about the mesh.
# supports()/cost() run at compile time and impl bodies at trace time, both
# with only (specs/inputs, attrs) in hand, so the engine publishes its mesh
# through this module-level context instead of threading it per call.
# --------------------------------------------------------------------------- #

_SERVING_MESH: Optional[Any] = None


@contextmanager
def serving_mesh(mesh):
    """Make ``mesh`` visible to the ``tp`` serving backends.

    The engine wraps both ``compile(mesh=...)`` (so ``supports()`` sees the
    mesh during backend selection) and every Program call (so the shard_map
    bodies see it at trace time) in this context."""
    global _SERVING_MESH
    prev = _SERVING_MESH
    _SERVING_MESH = mesh
    try:
        yield mesh
    finally:
        _SERVING_MESH = prev


def current_serving_mesh():
    """The mesh published by the innermost :func:`serving_mesh` (or None)."""
    return _SERVING_MESH


def _tp_state():
    """(mesh, degree) when a serving mesh with a >1 "model" axis is active,
    else (None, 1)."""
    mesh = current_serving_mesh()
    if mesh is None or "model" not in getattr(mesh, "axis_names", ()):
        return None, 1
    tp = int(mesh.shape["model"])
    return (mesh, tp) if tp > 1 else (None, 1)


# --------------------------------------------------------------------------- #
# embedding — inputs (ids (B,T) int32, table (V,D))
# --------------------------------------------------------------------------- #

def _embedding_shape(specs, attrs):
    ids, table = specs
    return [TensorSpec(tuple(ids.shape) + (table.shape[1],), table.dtype)]


def _embedding_cost(specs, attrs):
    out = _embedding_shape(specs, attrs)[0]
    # gather: reads one table row per token + writes the output
    return Cost(flops=0.0, bytes=2.0 * out.nbytes + specs[0].nbytes)


defop("embedding", _embedding_shape, _embedding_cost,
      doc="token embedding lookup; inputs (ids (B,T) int32, table (V,D))")


@impl("embedding", "ref")
def _embedding_ref(inputs, attrs):
    ids, table = inputs
    return [jnp.take(table, ids, axis=0)]


def _embedding_xla_cost(specs, attrs):
    """One-hot matmul: 2*N*V*D flops plus the materialised (N, V)
    one-hot, traded against the gather's pure byte cost."""
    ids, table = specs
    v, d = table.shape
    n = ids.nelems
    out = _embedding_shape(specs, attrs)[0]
    return Cost(flops=2.0 * n * v * d,
                bytes=table.nbytes + out.nbytes + 4.0 * n * v)


@impl("embedding", "xla", cost_fn=_embedding_xla_cost,
      note="fused one-hot matmul: row select on the MXU instead of a gather "
           "(exact — 0/1 weights select rows bit-for-bit)")
def _embedding_xla(inputs, attrs):
    ids, table = inputs
    # clamp like jit-mode jnp.take does, so out-of-range ids pick the
    # nearest valid row instead of one_hot's all-zero row
    ids = jnp.clip(ids, 0, table.shape[0] - 1)
    onehot = jax.nn.one_hot(ids, table.shape[0], dtype=table.dtype)
    return [jnp.tensordot(onehot, table, axes=1, precision=_precision(attrs))]


def embedding(ids, table, *, backend: str = "ref", **kw):
    return get_impl("embedding", backend)([ids, table], kw)[0]


# --------------------------------------------------------------------------- #
# cache_update — inputs (cache (B,S,H,D), new (B,T,H,D), start (B,), n_new (B,))
# --------------------------------------------------------------------------- #

def _cache_update_shape(specs, attrs):
    cache, new = specs[0], specs[1]
    if cache.shape[0] != new.shape[0] or cache.shape[2:] != new.shape[2:]:
        raise ValueError(f"cache_update mismatch: {cache.shape} vs {new.shape}")
    if new.shape[1] > cache.shape[1]:
        raise ValueError(f"chunk {new.shape[1]} exceeds cache cap {cache.shape[1]}")
    return [cache]


def _cache_update_cost(specs, attrs):
    new = specs[1]
    # read-modify-write of T rows per sequence; the rest of the cache is
    # untouched (aliasing is XLA's job under jit)
    return Cost(flops=0.0, bytes=3.0 * new.nbytes + _bytes(specs[2:]))


defop("cache_update", _cache_update_shape, _cache_update_cost,
      doc="scatter n_new K/V rows into a cache at per-sequence offsets; "
          "inputs (cache (B,S,H,D), new (B,T,H,D), start (B,), n_new (B,))")


@impl("cache_update", "ref",
      note="vmap'd row scatter with masked rows dropped; n_new==0 slots "
           "are exact no-ops.  (Masked rows used to clip to cap-1 and "
           "re-write it — a duplicate-index scatter that corrupted the "
           "last cache row when a ragged final chunk ended exactly at "
           "capacity.)")
def _cache_update_ref(inputs, attrs):
    cache, new, start, n_new = inputs
    t = new.shape[1]
    cap = cache.shape[1]

    def one(c, x, s, n):
        idx = s + jnp.arange(t)
        # rows at or past n are padding: send them out of bounds so the
        # scatter drops them instead of clipping onto a real row
        idx = jnp.where(jnp.arange(t) < n, jnp.clip(idx, 0, cap - 1), cap)
        return c.at[idx].set(x, mode="drop")

    return [jax.vmap(one)(cache, new, start, n_new)]


@impl("cache_update", "xla",
      note="per-slot lax.dynamic_update_slice of the mask-merged chunk; "
           "matches ref exactly on the engine contract 0 <= start and "
           "start + n_new <= cap (a final ragged chunk may start past "
           "cap - T — the slice is shifted back and the chunk re-aligned)")
def _cache_update_xla(inputs, attrs):
    cache, new, start, n_new = inputs
    t = new.shape[1]
    cap = cache.shape[1]

    def one(c, x, s, n):
        # a ragged final chunk can have s > cap - t while still writing
        # only n <= cap - s valid rows; shift the fixed-size slice window
        # back into bounds and place the chunk at its offset inside it
        s_c = jnp.clip(s, 0, cap - t)
        shift = s - s_c
        cur = jax.lax.dynamic_slice_in_dim(c, s_c, t, axis=0)
        j = jnp.arange(t)
        src = jnp.take(x, jnp.clip(j - shift, 0, t - 1), axis=0)
        mask = ((j >= shift) & (j < shift + n)).reshape(
            (t,) + (1,) * (x.ndim - 1))
        return jax.lax.dynamic_update_slice_in_dim(
            c, jnp.where(mask, src, cur), s_c, axis=0)

    return [jax.vmap(one)(cache, new, start, n_new)]


def cache_update(cache, new, start, n_new, *, backend: str = "ref", **kw):
    return get_impl("cache_update", backend)([cache, new, start, n_new], kw)[0]


# --------------------------------------------------------------------------- #
# chunk_attention — inputs (q (B,T,Hq,D), k (B,S,Hk,D), v (B,S,Hk,D), start (B,))
# --------------------------------------------------------------------------- #

def _chunk_attn_shape(specs, attrs):
    return [specs[0]]


def _chunk_attn_cost(specs, attrs):
    q, k = specs[0], specs[1]
    b, t, hq, d = q.shape
    s = k.shape[1]
    return Cost(flops=4.0 * b * hq * t * s * d, bytes=_bytes(specs) + q.nbytes)


defop("chunk_attention", _chunk_attn_shape, _chunk_attn_cost,
      doc="chunked-prefill attention: query t (absolute position start+t) "
          "attends cache keys at positions <= start+t; "
          "inputs (q (B,T,Hq,D), k (B,S,Hk,D), v, start (B,)); attrs: scale")


def _chunk_attn_scale(attrs, d: int) -> float:
    # NOT `attrs.get("scale") or default`: an explicit scale=0.0 is falsy
    # but meaningful (uniform attention over the allowed positions)
    scale = attrs.get("scale")
    return (1.0 / math.sqrt(d)) if scale is None else scale


def _chunk_visible(start, t: int, s: int, attrs: Attrs):
    """(B, T, S) bool: query t of a slot (absolute position start + t)
    sees key j iff j <= start + t and, with a ``window`` attr, also
    start + t - j < window."""
    qpos = start[:, None] + jnp.arange(t)[None, :]              # (B, T)
    keys = jnp.arange(s)[None, None, :]
    allowed = keys <= qpos[:, :, None]
    if attrs.get("window"):
        allowed &= keys > qpos[:, :, None] - attrs["window"]
    return allowed


def _chunk_attn_ref_cost(specs, attrs):
    """Adds the oracle's materialisation traffic: GQA-repeated K/V in
    fp32 plus the dense (B, Hq, T, S) logits and probability tensors."""
    q, k = specs[0], specs[1]
    b, t, hq, d = q.shape
    s = k.shape[1]
    base = _chunk_attn_cost(specs, attrs)
    extra = 4.0 * (2.0 * b * s * hq * d + 2.0 * b * hq * t * s)
    return Cost(flops=base.flops, bytes=base.bytes + extra)


@impl("chunk_attention", "ref", cost_fn=_chunk_attn_ref_cost,
      note="dense offset-causal masked attention in fp32 (the oracle)")
def _chunk_attention_ref(inputs, attrs):
    q, k, v, start = inputs
    b, t, hq, d = q.shape
    s = k.shape[1]
    scale = _chunk_attn_scale(attrs, d)
    prec = _precision(attrs)
    kf = R._repeat_kv(k, hq).astype(jnp.float32)
    vf = R._repeat_kv(v, hq).astype(jnp.float32)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale, kf,
                        precision=prec)
    allowed = _chunk_visible(start, t, s, attrs)                # (B, T, S)
    logits = jnp.where(allowed[:, None, :, :], logits, R._NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, vf, precision=prec)
    return [o.astype(q.dtype)]


@impl("chunk_attention", "xla",
      note="GQA grouped inside the einsum — the repeated-KV expansion is "
           "never materialised; XLA fuses mask+softmax")
def _chunk_attention_xla(inputs, attrs):
    q, k, v, start = inputs
    b, t, hq, d = q.shape
    s, hk = k.shape[1], k.shape[2]
    assert hq % hk == 0, (hq, hk)
    g = hq // hk
    scale = _chunk_attn_scale(attrs, d)
    prec = _precision(attrs)
    qg = (q.astype(jnp.float32) * scale).reshape(b, t, hk, g, d)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k.astype(jnp.float32),
                        precision=prec)
    allowed = _chunk_visible(start, t, s, attrs)                # (B, T, S)
    logits = jnp.where(allowed[:, None, None, :, :], logits, R._NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32),
                   precision=prec)
    return [o.reshape(b, t, hq, d).astype(q.dtype)]


def _chunk_attn_pallas_supports(specs, attrs):
    """T % block_q == 0, S % block_kv == 0 (blocks clamped to the sequence
    lengths) and Hq divisible by Hk (whole GQA groups)."""
    q, k = specs[0], specs[1]
    bq = min(int(attrs.get("block_q", 256)), q.shape[1])
    bkv = min(int(attrs.get("block_kv", 512)), k.shape[1])
    return (q.shape[1] % bq == 0 and k.shape[1] % bkv == 0
            and q.shape[2] % k.shape[2] == 0)


@impl("chunk_attention", "pallas", supports=_chunk_attn_pallas_supports,
      note="flash-style online-softmax kernel; per-sequence offset-causal "
           "masking, fully-masked KV blocks skipped")
def _chunk_attention_pallas(inputs, attrs):
    q, k, v, start = inputs
    return [flash_chunk_attention(
        q, k, v, start, scale=attrs.get("scale"),
        block_q=int(attrs.get("block_q", 256)),
        block_kv=int(attrs.get("block_kv", 512)),
        interpret=attrs.get("interpret", pallas_interpret()))]


def chunk_attention(q, k, v, start, *, scale=None, backend: str = "ref", **kw):
    return get_impl("chunk_attention", backend)(
        [q, k, v, start], {"scale": scale, **kw})[0]


# --------------------------------------------------------------------------- #
# Paged serving ops — K/V rows live in a shared page pool
# (n_blocks, page_size, Hk*D) and each sequence reaches its rows through an
# int32 block table (B, max_pages): logical page -> physical block.  The
# pool is lane-dense on purpose: with Hk*D as the minor dimension the TPU
# keeps it page-major ({2,1,0}), so the scatter of new rows updates the
# donated buffer in place and the gather reads whole pages as they lie.  A
# (.., Hk, D) pool with D off the 128-lane tiling (96, 160) would be laid
# out block-minor, and each step would copy every layer's whole pool into
# the scatter's layout and back.  Head h owns lanes h*D .. h*D+D-1.
# The engine side of the contract (allocation, refcounts, prefix reuse,
# CoW) lives in repro.runtime.kv_cache; these ops only move and read rows.
# Garbage table entries (unallocated logical pages, filled with 0) are
# harmless: reads of those positions are masked by start/lengths, writes
# never target them (start .. start+n_new-1 always lies in allocated pages).
# --------------------------------------------------------------------------- #

def _pool_heads(q, pages) -> int:
    """Kv heads of a flat (N, P, Hk*D) pool read with queries whose last
    dimension is D; 0 when the pool's row is not whole heads."""
    d, hd = q.shape[-1], pages.shape[-1]
    return hd // d if len(pages.shape) == 3 and hd % d == 0 else 0


def _whole_groups(q, pages) -> bool:
    """The pool's rows are whole kv heads and Hq is whole GQA groups."""
    hk = _pool_heads(q, pages)
    return hk > 0 and q.shape[-2] % hk == 0


def _split_heads(x, d):
    """(..., Hk*D) -> (..., Hk, D)."""
    return x.reshape(*x.shape[:-1], x.shape[-1] // d, d)


def _gather_pages(pages, tables):
    """(N, P, Hk*D) pool + (B, MP) tables -> dense (B, MP*P, Hk*D) view.

    The engine's tables hold valid block ids only (unallocated entries are
    0), so the gather promises in-bounds indices: ``mode="clip"`` lowers
    to a plain gather with no fill mask over the gathered view."""
    g = jnp.take(pages, tables, axis=0, mode="clip")        # (B, MP, P, HD)
    return g.reshape(tables.shape[0], tables.shape[1] * pages.shape[1],
                     pages.shape[2])


def _gathered_bytes(pages_spec, tables_spec) -> float:
    """HBM bytes of one gathered dense K or V view."""
    n, p, hd = pages_spec.shape
    b, mp = tables_spec.shape
    itemsize = pages_spec.nbytes / max(pages_spec.nelems, 1)
    return float(b * mp * p * hd) * itemsize


# ---- paged_cache_update --------------------------------------------------- #
# inputs (pages (N,P,Hk*D), new (B,T,Hk,D), tables (B,MP) i32, start, n_new)

def _paged_update_shape(specs, attrs):
    pages, new, tables = specs[0], specs[1], specs[2]
    if len(pages.shape) != 3 or pages.shape[2] != new.shape[2] * new.shape[3]:
        raise ValueError(f"page/new head mismatch: {pages.shape} vs {new.shape}")
    if new.shape[0] != tables.shape[0]:
        raise ValueError(f"batch mismatch: {new.shape} vs {tables.shape}")
    return [pages]


def _paged_update_cost(specs, attrs):
    new = specs[1]
    # read-modify-write of T rows per sequence through the table
    return Cost(flops=0.0, bytes=3.0 * new.nbytes + _bytes(specs[2:]))


defop("paged_cache_update", _paged_update_shape, _paged_update_cost,
      doc="scatter n_new K/V rows into a shared page pool through per-"
          "sequence block tables; inputs (pages (N,P,Hk*D), new (B,T,Hk,D), "
          "tables (B,MP) int32, start (B,), n_new (B,))")


def _paged_rows(tables, start, t, p, n_blocks):
    """Physical (block, row) targets for T rows per slot from ``start``;
    rows at or past ``n_new`` get block index N (dropped by the scatter)."""
    mp = tables.shape[1]
    pos = start[:, None] + jnp.arange(t)[None, :]              # (B, T)
    blk = jnp.take_along_axis(tables, jnp.clip(pos // p, 0, mp - 1), axis=1)
    return jnp.clip(blk, 0, n_blocks - 1), pos % p


def _flat_rows(new):
    """(B, T, Hk, D) rows -> (B, T, Hk*D), the pool's row shape."""
    return jnp.asarray(new).reshape(new.shape[0], new.shape[1], -1)


@impl("paged_cache_update", "ref",
      note="per-slot python loop of masked row scatters (the oracle); "
          "n_new==0 slots are exact no-ops")
def _paged_cache_update_ref(inputs, attrs):
    pages, new, tables, start, n_new = inputs
    n_blocks, p = pages.shape[0], pages.shape[1]
    b, t = new.shape[0], new.shape[1]
    blk, row = _paged_rows(tables, start, t, p, n_blocks)
    rows = _flat_rows(new)
    out = jnp.asarray(pages)
    for bi in range(b):
        valid = jnp.arange(t) < n_new[bi]
        tgt = jnp.where(valid, blk[bi], n_blocks)      # OOB rows are dropped
        out = out.at[tgt, row[bi]].set(rows[bi], mode="drop")
    return [out]


@impl("paged_cache_update", "xla",
      note="one flat (B*T)-row scatter into the donated pool, in place; "
           "bit-identical to ref because write targets are unique (each "
           "writable page belongs to one sequence)")
def _paged_cache_update_xla(inputs, attrs):
    pages, new, tables, start, n_new = inputs
    n_blocks, p = pages.shape[0], pages.shape[1]
    b, t = new.shape[0], new.shape[1]
    blk, row = _paged_rows(tables, start, t, p, n_blocks)
    valid = jnp.arange(t)[None, :] < jnp.asarray(n_new)[:, None]
    tgt = jnp.where(valid, blk, n_blocks)
    return [jnp.asarray(pages).at[tgt.reshape(-1), row.reshape(-1)].set(
        _flat_rows(new).reshape(b * t, -1), mode="drop")]


def paged_cache_update(pages, new, tables, start, n_new, *,
                       backend: str = "ref", **kw):
    return get_impl("paged_cache_update", backend)(
        [pages, new, tables, start, n_new], kw)[0]


# ---- paged_chunk_attention ------------------------------------------------ #
# inputs (q (B,T,Hq,D), pages_k (N,P,Hk*D), pages_v, tables (B,MP), start)

def _paged_chunk_shape(specs, attrs):
    return [specs[0]]


def _paged_chunk_cost(specs, attrs):
    q, pk, tables = specs[0], specs[1], specs[3]
    b, t, hq, d = q.shape
    s = tables.shape[1] * pk.shape[1]
    gathered = 2.0 * _gathered_bytes(pk, tables)      # stream K and V once
    return Cost(flops=4.0 * b * hq * t * s * d,
                bytes=2.0 * q.nbytes + tables.nbytes + gathered)


defop("paged_chunk_attention", _paged_chunk_shape, _paged_chunk_cost,
      doc="chunked-prefill attention reading K/V through block tables; "
          "inputs (q (B,T,Hq,D), pages_k (N,P,Hk*D), pages_v, "
          "tables (B,MP) int32, start (B,)); attrs: scale")


def _paged_chunk_ref_cost(specs, attrs):
    """Charges the materialised dense gather plus the ref oracle's
    GQA-repeated K/V and dense logits/probability tensors."""
    q, pk, tables = specs[0], specs[1], specs[3]
    b, t, hq, d = q.shape
    s = tables.shape[1] * pk.shape[1]
    base = _paged_chunk_cost(specs, attrs)
    extra = 2.0 * 2.0 * _gathered_bytes(pk, tables)   # written then re-read
    extra += 4.0 * (2.0 * b * s * hq * d + 2.0 * b * hq * t * s)
    return Cost(flops=base.flops, bytes=base.bytes + extra)


@impl("paged_chunk_attention", "ref", cost_fn=_paged_chunk_ref_cost,
      note="gather pages to a dense view, then the dense fp32 offset-"
           "causal oracle")
def _paged_chunk_attention_ref(inputs, attrs):
    q, pk, pv, tables, start = inputs
    d = q.shape[-1]
    return _chunk_attention_ref(
        [q, _split_heads(_gather_pages(pk, tables), d),
         _split_heads(_gather_pages(pv, tables), d), start], attrs)


def _paged_chunk_xla_cost(specs, attrs):
    """Charges the materialised dense gather; attention itself stays
    GQA-grouped (no repeated-KV expansion)."""
    q, pk, tables = specs[0], specs[1], specs[3]
    base = _paged_chunk_cost(specs, attrs)
    return Cost(flops=base.flops,
                bytes=base.bytes + 2.0 * 2.0 * _gathered_bytes(pk, tables))


@impl("paged_chunk_attention", "xla", cost_fn=_paged_chunk_xla_cost,
      note="gather pages to a dense view + the GQA-grouped einsum "
           "(repeated-KV never materialised)")
def _paged_chunk_attention_xla(inputs, attrs):
    """Splits the gathered (B, S, Hk*D) view into heads, one relayout of
    the view on the TPU.  With T query rows per slot the block-diagonal
    form of the decode path (:func:`_decode_attention_flat`) would be a
    real matmul Hk times over, with a (B, T*Hq, Hk*D) product to reduce:
    dearer than the relayout."""
    q, pk, pv, tables, start = inputs
    d = q.shape[-1]
    return _chunk_attention_xla(
        [q, _split_heads(_gather_pages(pk, tables), d),
         _split_heads(_gather_pages(pv, tables), d), start], attrs)


# The paged Pallas kernels keep their block table, the per-row lengths or
# starts and (int8) both (N, Hk) scale sidecars whole in SMEM.  A v5e core
# has 1 MiB of it and Mosaic refuses a kernel that asks for more; half is
# the budget, leaving the rest to Mosaic's own scalars.
_SMEM_BUDGET = 512 * 1024


def _smem_fits(q, tables, *scales) -> bool:
    """SMEM bytes of the table, one int32 per (batch, q head) row (an upper
    bound for the per-(batch, kv head) decode lengths) and the sidecars."""
    rows = 4 * q.shape[0] * q.shape[-2]
    return rows + tables.nbytes + sum(s.nbytes for s in scales) <= _SMEM_BUDGET


def _paged_chunk_pallas_supports(specs, attrs):
    """T % block_q == 0 (block clamped to T), page_size % 8 == 0 (TPU
    sublane tiling of one page per KV step), Hq divisible by Hk, the
    SMEM operands within budget, and no attention window."""
    q, pk = specs[0], specs[1]
    bq = min(int(attrs.get("block_q", 256)), q.shape[1])
    return (q.shape[1] % bq == 0 and pk.shape[1] % 8 == 0
            and _whole_groups(q, pk) and _smem_fits(q, specs[3])
            and _no_window(specs, attrs))


@impl("paged_chunk_attention", "pallas",
      supports=_paged_chunk_pallas_supports,
      note="flash kernel reading pages in place via the scalar-prefetched "
           "block table — the dense gather copy never exists "
           "(flash_paged_chunk_attention)")
def _paged_chunk_attention_pallas(inputs, attrs):
    from repro.kernels.flash_attention import flash_paged_chunk_attention
    q, pk, pv, tables, start = inputs
    return [flash_paged_chunk_attention(
        q, pk, pv, tables, start, scale=attrs.get("scale"),
        block_q=int(attrs.get("block_q", 256)),
        interpret=attrs.get("interpret", pallas_interpret()))]


def paged_chunk_attention(q, pages_k, pages_v, tables, start, *, scale=None,
                          backend: str = "ref", **kw):
    return get_impl("paged_chunk_attention", backend)(
        [q, pages_k, pages_v, tables, start], {"scale": scale, **kw})[0]


# ---- paged_decode_attention ----------------------------------------------- #
# inputs (q (B,Hq,D), pages_k (N,P,Hk*D), pages_v, tables (B,MP), lengths)

def _paged_dec_shape(specs, attrs):
    return [specs[0]]


def _paged_dec_cost(specs, attrs):
    q, pk, tables = specs[0], specs[1], specs[3]
    b, hq, d = q.shape
    s = tables.shape[1] * pk.shape[1]
    gathered = 2.0 * _gathered_bytes(pk, tables)
    return Cost(flops=4.0 * b * hq * s * d,
                bytes=2.0 * q.nbytes + tables.nbytes + gathered)


defop("paged_decode_attention", _paged_dec_shape, _paged_dec_cost,
      doc="single-token attention reading the KV cache through block "
          "tables; inputs (q (B,Hq,D), pages_k (N,P,Hk*D), pages_v, "
          "tables (B,MP) int32, lengths (B,)); attrs: scale")


def _paged_dec_ref_cost(specs, attrs):
    """Adds the materialised dense gather and the oracle's GQA-repeated
    K/V to the op's streaming cost."""
    q, pk, tables = specs[0], specs[1], specs[3]
    b, hq, d = q.shape
    s = tables.shape[1] * pk.shape[1]
    base = _paged_dec_cost(specs, attrs)
    extra = 2.0 * 2.0 * _gathered_bytes(pk, tables)
    extra += 4.0 * (2.0 * b * s * hq * d)
    return Cost(flops=base.flops, bytes=base.bytes + extra)


@impl("paged_decode_attention", "ref", cost_fn=_paged_dec_ref_cost,
      note="gather pages to a dense view + the dense fp32 decode oracle")
def _paged_decode_attention_ref(inputs, attrs):
    q, pk, pv, tables, lengths = inputs
    k = _split_heads(_gather_pages(pk, tables), q.shape[-1])
    v = _split_heads(_gather_pages(pv, tables), q.shape[-1])
    return [R.decode_attention_ref(q, k, v, lengths,
                                   scale=attrs.get("scale"),
                                   window=attrs.get("window"),
                                   precision=_precision(attrs))]


def _paged_dec_xla_cost(specs, attrs):
    """Charges the materialised dense gather on top of the op's
    streaming cost.  The block-diagonal einsum's zero products are not
    charged: with one query row per slot the bytes bound the step."""
    q, pk, tables = specs[0], specs[1], specs[3]
    base = _paged_dec_cost(specs, attrs)
    return Cost(flops=base.flops,
                bytes=base.bytes + 2.0 * 2.0 * _gathered_bytes(pk, tables))


def _decode_attention_xla_dense(q, k, v, lengths, attrs):
    """GQA-grouped einsum decode over dense (already gathered) K/V."""
    b, hq, d = q.shape
    s, hk = k.shape[1], k.shape[2]
    assert hq % hk == 0, (hq, hk)
    g = hq // hk
    scale = _chunk_attn_scale(attrs, d)
    qg = (q.astype(jnp.float32) * scale).reshape(b, hk, g, d)
    logits = jnp.einsum("bkgd,bskd->bkgs", qg, k.astype(jnp.float32))
    allowed = jnp.arange(s)[None, None, None, :] < lengths[:, None, None, None]
    logits = jnp.where(allowed, logits, R._NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v.astype(jnp.float32))
    return o.reshape(b, hq, d).astype(q.dtype)


def _head_mask(hq: int, hk: int):
    """(Hq, Hk) bool: query head i reads kv head i // (Hq / Hk)."""
    assert hq % hk == 0, (hq, hk)
    return (jnp.arange(hq)[:, None] // (hq // hk)) == jnp.arange(hk)[None, :]


def _decode_attention_flat(q, k, v, lengths, attrs):
    """Decode attention over gathered (B, S, Hk*D) K/V as they lie.

    Splitting the lane axis Hk*D into (Hk, D) would be a relayout copy of
    the whole gathered view on the TPU (D = 96 or 160 is off the 128-lane
    tiling).  Instead each query row is spread over all Hk*D lanes, zero
    outside its kv head, so both matmuls contract over or produce the
    lane axis; each output row then keeps its own head's D lanes.  The
    added products are exact zeros.  With one query row per slot the
    matmuls are bound by reading K and V, so Hk times the MXU work costs
    less than the relayout would.

    The precision keeps the arithmetic of the grouped einsum in
    :func:`_decode_attention_xla_dense` on the TPU.  With whole GQA groups
    (Hq > Hk) that einsum is a matmul at the default precision, operands
    rounded to bf16 and sums in f32, and so is this one.  With one query
    row per kv head (Hq == Hk) it is a matrix-vector product per head,
    which XLA computes as an f32 multiply and reduce; the block-diagonal
    form is a matmul there, so it runs at ``HIGHEST`` to stay f32.  A
    node's own ``precision`` attr takes the place of both rules.  With a
    ``window`` attr the query sees only the last ``window`` rows."""
    b, hq, d = q.shape
    s, hkd = k.shape[1], k.shape[2]
    hk = hkd // d
    prec = _precision(attrs)
    if prec is None and hq == hk:
        prec = jax.lax.Precision.HIGHEST
    own = _head_mask(hq, hk)[:, :, None]                        # (Hq, Hk, 1)
    qs = (q.astype(jnp.float32) * _chunk_attn_scale(attrs, d))[:, :, None]
    qbd = jnp.where(own, qs, 0.0).reshape(b, hq, hkd)          # (B, Hq, HkD)
    logits = jnp.einsum("bqj,bsj->bqs", qbd, k.astype(jnp.float32),
                        precision=prec)
    keys = jnp.arange(s)[None, None, :]
    allowed = keys < lengths[:, None, None]
    if attrs.get("window"):
        allowed &= keys >= lengths[:, None, None] - attrs["window"]
    logits = jnp.where(allowed, logits, R._NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bqs,bsj->bqj", p, v.astype(jnp.float32),
                   precision=prec)                              # (B, Hq, HkD)
    o = jnp.where(own, o.reshape(b, hq, hk, d), 0.0).sum(axis=2)
    return o.astype(q.dtype)


@impl("paged_decode_attention", "xla", cost_fn=_paged_dec_xla_cost,
      note="gather pages to a lane-dense (B, S, Hk*D) view + a block-"
           "diagonal einsum over it as it lies (no head relayout), "
           "length-masked")
def _paged_decode_attention_xla(inputs, attrs):
    q, pk, pv, tables, lengths = inputs
    return [_decode_attention_flat(q, _gather_pages(pk, tables),
                                   _gather_pages(pv, tables), lengths, attrs)]


def _paged_dec_pallas_supports(specs, attrs):
    """page_size % 8 == 0 (TPU sublane tiling of one page per KV step), Hq
    divisible by Hk (whole GQA groups), the SMEM operands within budget,
    and no attention window."""
    q, pk = specs[0], specs[1]
    return (pk.shape[1] % 8 == 0 and _whole_groups(q, pk)
            and _smem_fits(q, specs[3]) and _no_window(specs, attrs))


@impl("paged_decode_attention", "pallas", supports=_paged_dec_pallas_supports,
      note="block-table-aware flash decode: pages streamed via scalar-"
           "prefetched table indices, online softmax, one KV read per GQA "
           "group (repro.kernels.flash_decode.flash_paged_decode)")
def _paged_decode_attention_pallas(inputs, attrs):
    from repro.kernels.flash_decode import flash_paged_decode
    q, pk, pv, tables, lengths = inputs
    return [flash_paged_decode(
        q, pk, pv, tables, lengths, scale=attrs.get("scale"),
        interpret=attrs.get("interpret", pallas_interpret()))]


def paged_decode_attention(q, pages_k, pages_v, tables, lengths, *,
                           scale=None, backend: str = "ref", **kw):
    return get_impl("paged_decode_attention", backend)(
        [q, pages_k, pages_v, tables, lengths], {"scale": scale, **kw})[0]


# --------------------------------------------------------------------------- #
# Quantized paged ops — pages stored int8 with a per-(page, kv-head) float32
# scale sidecar (N, Hk).  Symmetric scheme: scale = absmax / 127, row = q *
# scale.  Scales only ever GROW (running per-page max): a write that raises a
# page's absmax requantizes that page's existing rows by old/new; pages whose
# scale did not change requantize by exactly 1.0, which is bit-exact, so
# prefix-shared pages keep identical bits across sequences.  An all-zero page
# keeps scale 0.0 and quantizes via a `scale > 0` guard (`x / 0` would be
# inf; `attrs.get("scale") or`-style falsy fallbacks are exactly the PR 4
# bug class this guard avoids).  The fp32 cache is never materialised: the
# attention backends dequantize after the gather (ref/xla) or in-register
# inside the online-softmax loop (pallas).
# --------------------------------------------------------------------------- #

_Q_MAX = 127.0


def _gather_pages_q(pages_q, scales, tables):
    """int8 (N,P,Hk*D) pages + (N,Hk) scales + (B,MP) tables -> dense fp32
    (B, MP*P, Hk*D) view, dequantized after the gather (per-sequence
    working set, never the whole pool)."""
    p, hkd = pages_q.shape[1], pages_q.shape[2]
    g = jnp.take(pages_q, tables, axis=0, mode="clip")       # (B,MP,P,HkD)
    sc = jnp.take(scales, tables, axis=0, mode="clip")       # (B,MP,Hk)
    # each head's scale repeated over its D lanes of the row
    g = g.astype(jnp.float32) * jnp.repeat(sc, hkd // sc.shape[-1],
                                           axis=-1)[:, :, None]
    return g.reshape(tables.shape[0], tables.shape[1] * p, hkd)


def _scale_bytes(specs) -> float:
    return float(sum(s.nbytes for s in specs if len(s.shape) == 2
                     and s.dtype == "float32"))


# ---- paged_cache_update_q ------------------------------------------------- #
# inputs (pages (N,P,Hk*D) int8, scales (N,Hk) f32, new (B,T,Hk,D) f32,
#         tables (B,MP) i32, start (B,), n_new (B,)) -> [pages, scales]

def _paged_update_q_shape(specs, attrs):
    pages, scales, new, tables = specs[0], specs[1], specs[2], specs[3]
    if pages.dtype != "int8":
        raise ValueError(f"quantized pages must be int8, got {pages.dtype}")
    if scales.shape != (pages.shape[0], new.shape[2]):
        raise ValueError(f"scales {scales.shape} != (N, Hk) "
                         f"({pages.shape[0]}, {new.shape[2]})")
    if len(pages.shape) != 3 or pages.shape[2] != new.shape[2] * new.shape[3]:
        raise ValueError(f"page/new head mismatch: {pages.shape} vs {new.shape}")
    if new.shape[0] != tables.shape[0]:
        raise ValueError(f"batch mismatch: {new.shape} vs {tables.shape}")
    return [pages, scales]


def _paged_update_q_cost(specs, attrs):
    """int8-honest traffic: RMW of the written rows at 1 byte/elem, the
    fp32 chunk read once, plus the full-pool requantize pass (read+write
    every int8 page and both scale sidecar states)."""
    pages, scales, new = specs[0], specs[1], specs[2]
    return Cost(flops=2.0 * pages.nelems,
                bytes=(2.0 * pages.nbytes + 3.0 * new.nelems + new.nbytes
                       + 3.0 * scales.nbytes + _bytes(specs[3:])))


defop("paged_cache_update_q", _paged_update_q_shape, _paged_update_q_cost,
      doc="quantize-on-write scatter into an int8 page pool with running "
          "per-(page, kv-head) max scales; inputs (pages (N,P,Hk*D) int8, "
          "scales (N,Hk) f32, new (B,T,Hk,D), tables (B,MP) int32, "
          "start (B,), n_new (B,)); outputs [pages, scales]")


def _quantize_rows(x, scale):
    """fp32 rows -> int8 given a broadcastable scale; scale==0 rows are
    all-zero by construction (scale is their absmax / 127)."""
    q = jnp.where(scale > 0, x / jnp.where(scale > 0, scale, 1.0), 0.0)
    return jnp.clip(jnp.round(q), -_Q_MAX, _Q_MAX).astype(jnp.int8)


def _paged_update_q_common(inputs):
    """Shared scale bookkeeping: returns (requantized pages fp32-exact in
    int8, new scales, int8 rows to scatter, blk, row, valid).  Order-
    independent: scales use a scatter-max, write targets are unique."""
    pages, scales, new, tables, start, n_new = inputs
    n_blocks, p = pages.shape[0], pages.shape[1]
    b, t = new.shape[0], new.shape[1]
    blk, row = _paged_rows(tables, start, t, p, n_blocks)
    valid = jnp.arange(t)[None, :] < jnp.asarray(n_new)[:, None]   # (B, T)
    tgt = jnp.where(valid, blk, n_blocks)                          # (B, T)
    # running per-(page, head) max: only written pages can grow
    row_amax = jnp.max(jnp.abs(new), axis=-1)                      # (B, T, H)
    row_scale = jnp.where(valid[..., None], row_amax / _Q_MAX, 0.0)
    new_scales = jnp.asarray(scales).at[tgt.reshape(-1)].max(
        row_scale.reshape(b * t, -1), mode="drop")
    # requantize the pool by old/new; untouched pages have ratio exactly
    # 1.0, so round(q * 1.0) == q and shared pages stay bit-identical
    ratio = jnp.where(new_scales > 0, jnp.asarray(scales) / new_scales, 1.0)
    pages_rq = jnp.clip(
        jnp.round(pages.astype(jnp.float32)
                  * jnp.repeat(ratio, new.shape[3], axis=-1)[:, None, :]),
        -_Q_MAX, _Q_MAX).astype(jnp.int8)
    # quantize the incoming rows with their target page's final scale
    tgt_scale = jnp.take(new_scales, jnp.clip(tgt, 0, n_blocks - 1),
                         axis=0)                                   # (B, T, H)
    q_rows = _flat_rows(_quantize_rows(jnp.asarray(new), tgt_scale[..., None]))
    return pages_rq, new_scales, q_rows, blk, row, valid, tgt


@impl("paged_cache_update_q", "ref",
      note="per-slot python loop of masked int8 row scatters after the "
           "shared scale-growth/requantize pass (the oracle)")
def _paged_cache_update_q_ref(inputs, attrs):
    pages = inputs[0]
    n_blocks = pages.shape[0]
    b, t = inputs[2].shape[0], inputs[2].shape[1]
    pages_rq, new_scales, q_rows, blk, row, valid, _ = \
        _paged_update_q_common(inputs)
    out = pages_rq
    for bi in range(b):
        tgt = jnp.where(valid[bi], blk[bi], n_blocks)   # OOB rows dropped
        out = out.at[tgt, row[bi]].set(q_rows[bi], mode="drop")
    return [out, new_scales]


@impl("paged_cache_update_q", "xla",
      note="one flat (B*T)-row int8 scatter; bit-identical to ref because "
           "write targets are unique and the scale pass is a scatter-max")
def _paged_cache_update_q_xla(inputs, attrs):
    new = inputs[2]
    b, t = new.shape[0], new.shape[1]
    pages_rq, new_scales, q_rows, blk, row, valid, tgt = \
        _paged_update_q_common(inputs)
    out = pages_rq.at[tgt.reshape(-1), row.reshape(-1)].set(
        q_rows.reshape(b * t, -1), mode="drop")
    return [out, new_scales]


def paged_cache_update_q(pages, scales, new, tables, start, n_new, *,
                         backend: str = "ref", **kw):
    return get_impl("paged_cache_update_q", backend)(
        [pages, scales, new, tables, start, n_new], kw)


# ---- paged_chunk_attention_q ---------------------------------------------- #
# inputs (q (B,T,Hq,D), pages_k (N,P,Hk*D) i8, k_scales (N,Hk) f32,
#         pages_v i8, v_scales, tables (B,MP) i32, start (B,))

def _check_q_pool(q, pk, ks):
    if pk.dtype != "int8":
        raise ValueError(f"quantized pages must be int8, got {pk.dtype}")
    if ks.shape != (pk.shape[0], _pool_heads(q, pk)):
        raise ValueError(f"k_scales {ks.shape} != (N, Hk)")


def _paged_chunk_q_shape(specs, attrs):
    _check_q_pool(*specs[:3])
    return [specs[0]]


def _paged_chunk_q_cost(specs, attrs):
    """Streams the gathered K/V once at 1 byte/elem (int8) plus the scale
    sidecars — the whole point of quantized pages on the memory-bound
    serving path."""
    q, pk, tables = specs[0], specs[1], specs[5]
    b, t, hq, d = q.shape
    s = tables.shape[1] * pk.shape[1]
    gathered = 2.0 * _gathered_bytes(pk, tables)      # int8 itemsize
    return Cost(flops=4.0 * b * hq * t * s * d,
                bytes=2.0 * q.nbytes + tables.nbytes + gathered
                      + _scale_bytes(specs))


defop("paged_chunk_attention_q", _paged_chunk_q_shape, _paged_chunk_q_cost,
      doc="chunked-prefill attention over int8 pages, dequantized with "
          "per-(page, kv-head) scales; inputs (q (B,T,Hq,D), pages_k int8, "
          "k_scales (N,Hk), pages_v int8, v_scales, tables (B,MP) int32, "
          "start (B,)); attrs: scale")


def _paged_chunk_q_gather_cost(specs, attrs):
    """Adds the materialised fp32 dequantized gather (written then re-read)
    on top of the int8 streaming cost."""
    q, pk, tables = specs[0], specs[1], specs[5]
    base = _paged_chunk_q_cost(specs, attrs)
    b, mp = tables.shape
    n, p, hd = pk.shape
    dense_f32 = 4.0 * b * mp * p * hd
    return Cost(flops=base.flops, bytes=base.bytes + 2.0 * 2.0 * dense_f32)


@impl("paged_chunk_attention_q", "ref", cost_fn=_paged_chunk_q_gather_cost,
      note="dequantize after the gather, then the dense fp32 offset-causal "
           "oracle")
def _paged_chunk_attention_q_ref(inputs, attrs):
    q, pk, ks, pv, vs, tables, start = inputs
    d = q.shape[-1]
    return _chunk_attention_ref(
        [q, _split_heads(_gather_pages_q(pk, ks, tables), d),
         _split_heads(_gather_pages_q(pv, vs, tables), d), start], attrs)


@impl("paged_chunk_attention_q", "xla", cost_fn=_paged_chunk_q_gather_cost,
      note="dequantize after the gather + the GQA-grouped einsum "
           "(repeated-KV never materialised)")
def _paged_chunk_attention_q_xla(inputs, attrs):
    q, pk, ks, pv, vs, tables, start = inputs
    d = q.shape[-1]
    return _chunk_attention_xla(
        [q, _split_heads(_gather_pages_q(pk, ks, tables), d),
         _split_heads(_gather_pages_q(pv, vs, tables), d), start], attrs)


def _paged_chunk_q_pallas_supports(specs, attrs):
    """T % block_q == 0 (block clamped to T), page_size % 8 == 0, Hq
    divisible by Hk (whole GQA groups) and the table plus both scale
    sidecars within the SMEM budget."""
    q, pk = specs[0], specs[1]
    bq = min(int(attrs.get("block_q", 256)), q.shape[1])
    return (q.shape[1] % bq == 0 and pk.shape[1] % 8 == 0
            and _whole_groups(q, pk)
            and _smem_fits(q, specs[5], specs[2], specs[4]))


@impl("paged_chunk_attention_q", "pallas",
      supports=_paged_chunk_q_pallas_supports,
      note="fused flash kernel: int8 K/V tiles stream through the scalar-"
           "prefetched block table and dequantize in-register inside the "
           "online-softmax loop (flash_paged_chunk_attention)")
def _paged_chunk_attention_q_pallas(inputs, attrs):
    from repro.kernels.flash_attention import flash_paged_chunk_attention
    q, pk, ks, pv, vs, tables, start = inputs
    return [flash_paged_chunk_attention(
        q, pk, pv, tables, start, k_scales=ks, v_scales=vs,
        scale=attrs.get("scale"),
        block_q=int(attrs.get("block_q", 256)),
        interpret=attrs.get("interpret", pallas_interpret()))]


def paged_chunk_attention_q(q, pages_k, k_scales, pages_v, v_scales, tables,
                            start, *, scale=None, backend: str = "ref", **kw):
    return get_impl("paged_chunk_attention_q", backend)(
        [q, pages_k, k_scales, pages_v, v_scales, tables, start],
        {"scale": scale, **kw})[0]


# ---- paged_decode_attention_q --------------------------------------------- #
# inputs (q (B,Hq,D), pages_k (N,P,Hk*D) i8, k_scales (N,Hk) f32,
#         pages_v i8, v_scales, tables (B,MP) i32, lengths (B,))

def _paged_dec_q_shape(specs, attrs):
    _check_q_pool(*specs[:3])
    return [specs[0]]


def _paged_dec_q_cost(specs, attrs):
    """Streams the gathered K/V once at 1 byte/elem (int8) plus the
    scale sidecars."""
    q, pk, tables = specs[0], specs[1], specs[5]
    b, hq, d = q.shape
    s = tables.shape[1] * pk.shape[1]
    gathered = 2.0 * _gathered_bytes(pk, tables)
    return Cost(flops=4.0 * b * hq * s * d,
                bytes=2.0 * q.nbytes + tables.nbytes + gathered
                      + _scale_bytes(specs))


defop("paged_decode_attention_q", _paged_dec_q_shape, _paged_dec_q_cost,
      doc="single-token attention over int8 pages, dequantized with "
          "per-(page, kv-head) scales; inputs (q (B,Hq,D), pages_k int8, "
          "k_scales (N,Hk), pages_v int8, v_scales, tables (B,MP) int32, "
          "lengths (B,)); attrs: scale")


def _paged_dec_q_gather_cost(specs, attrs):
    """Adds the materialised fp32 dequantized gather on top of the int8
    streaming cost."""
    q, pk, tables = specs[0], specs[1], specs[5]
    base = _paged_dec_q_cost(specs, attrs)
    b, mp = tables.shape
    n, p, hd = pk.shape
    dense_f32 = 4.0 * b * mp * p * hd
    return Cost(flops=base.flops, bytes=base.bytes + 2.0 * 2.0 * dense_f32)


@impl("paged_decode_attention_q", "ref", cost_fn=_paged_dec_q_gather_cost,
      note="dequantize after the gather + the dense fp32 decode oracle")
def _paged_decode_attention_q_ref(inputs, attrs):
    q, pk, ks, pv, vs, tables, lengths = inputs
    k = _split_heads(_gather_pages_q(pk, ks, tables), q.shape[-1])
    v = _split_heads(_gather_pages_q(pv, vs, tables), q.shape[-1])
    return [R.decode_attention_ref(q, k, v, lengths,
                                   scale=attrs.get("scale"))]


@impl("paged_decode_attention_q", "xla", cost_fn=_paged_dec_q_gather_cost,
      note="dequantize after the gather + a block-diagonal einsum over "
           "the lane-dense view (no head relayout), length-masked")
def _paged_decode_attention_q_xla(inputs, attrs):
    q, pk, ks, pv, vs, tables, lengths = inputs
    return [_decode_attention_flat(q, _gather_pages_q(pk, ks, tables),
                                   _gather_pages_q(pv, vs, tables), lengths,
                                   attrs)]


def _paged_dec_q_pallas_supports(specs, attrs):
    """page_size % 8 == 0 (TPU sublane tiling of one page per KV step), Hq
    divisible by Hk (whole GQA groups) and the table plus both scale
    sidecars within the SMEM budget."""
    q, pk = specs[0], specs[1]
    return (pk.shape[1] % 8 == 0 and _whole_groups(q, pk)
            and _smem_fits(q, specs[5], specs[2], specs[4]))


@impl("paged_decode_attention_q", "pallas",
      supports=_paged_dec_q_pallas_supports,
      note="fused flash decode: int8 pages stream via scalar-prefetched "
           "table indices, per-(page, head) scales are read from SMEM and "
           "dequant happens in-register (flash_paged_decode)")
def _paged_decode_attention_q_pallas(inputs, attrs):
    from repro.kernels.flash_decode import flash_paged_decode
    q, pk, ks, pv, vs, tables, lengths = inputs
    return [flash_paged_decode(
        q, pk, pv, tables, lengths, k_scales=ks, v_scales=vs,
        scale=attrs.get("scale"),
        interpret=attrs.get("interpret", pallas_interpret()))]


def paged_decode_attention_q(q, pages_k, k_scales, pages_v, v_scales, tables,
                             lengths, *, scale=None, backend: str = "ref",
                             **kw):
    return get_impl("paged_decode_attention_q", backend)(
        [q, pages_k, k_scales, pages_v, v_scales, tables, lengths],
        {"scale": scale, **kw})[0]


# --------------------------------------------------------------------------- #
# moe_ffn — the routed-expert SwiGLU FFN of a mixture-of-experts layer,
# dropless: every live row reaches all of its top_k experts, however skewed
# the routing (a capacity-bucketed dispatch would drop rows past each
# expert's capacity, and the result would then depend on the batch).
# inputs (x (B,T,d), n_new (B,), router (d,E), w_gate (E,d,f), w_up (E,d,f),
#         w_down (E,f,d)) -> [y (B,T,d), counts (1,E) int32]
# Rows at or past ``n_new`` (chunk padding, idle slots) are routed nowhere:
# their y is 0 and ``counts`` holds the live rows sent to each expert.
# attrs: top_k, norm_topk (softmax over the chosen experts' logits, else
# over all of them), precision.
# --------------------------------------------------------------------------- #

def _moe_shape(specs, attrs):
    x, n_new, router, wg, wu, wd = specs
    e, d, f = wg.shape
    if (x.shape[-1], router.shape) != (d, (d, e)) or wu.shape != wg.shape \
            or wd.shape != (e, f, d) or n_new.shape != (x.shape[0],):
        raise ValueError(f"moe_ffn shapes: x {x.shape}, n_new {n_new.shape}, "
                         f"router {router.shape}, experts {wg.shape} "
                         f"{wu.shape} {wd.shape}")
    if not 0 < int(attrs.get("top_k", 0)) <= e:
        raise ValueError(f"moe_ffn top_k {attrs.get('top_k')} of {e}")
    return [x, TensorSpec((1, e), "int32")]


def _moe_hit(rows: int, k: int, e: int) -> float:
    """Experts hit by ``rows`` rows routed uniformly to k of e each."""
    return e * (1.0 - (1.0 - k / e) ** rows)


def _moe_cost(specs, attrs):
    """The dropless grouped form: the router over every row, k experts'
    SwiGLU per row, and the weights of the experts that uniform routing
    hits read once each, plus the grouped rows in and out."""
    x, router, wg = specs[0], specs[2], specs[3]
    e, d, f = wg.shape
    k = int(attrs["top_k"])
    n = x.nelems // d
    itemsize = x.nbytes / x.nelems
    expert = 3.0 * wg.nbytes / e
    return Cost(flops=2.0 * n * d * e + 6.0 * n * k * d * f,
                bytes=(router.nbytes + _moe_hit(n, k, e) * expert
                       + itemsize * n * k * (2.0 * d + 3.0 * f)
                       + 2.0 * x.nbytes))


defop("moe_ffn", _moe_shape, _moe_cost,
      doc="dropless routed-expert SwiGLU FFN: top_k of E experts per live "
          "row, weighted by the router's softmax; inputs (x (B,T,d), "
          "n_new (B,), router (d,E), w_gate (E,d,f), w_up (E,d,f), "
          "w_down (E,f,d)); outputs [y (B,T,d), counts (1,E) int32]; "
          "attrs: top_k, norm_topk, precision")


def _moe_route(x, n_new, router, attrs):
    """Flat rows (N, d), live mask (N,), the top_k experts of each row
    (N, k; ties to the lower index) and their weights (N, k)."""
    b, t, d = x.shape
    rows = x.reshape(b * t, d)
    live = (jnp.arange(t)[None, :] < n_new[:, None]).reshape(-1)
    logits = jnp.matmul(rows, router, precision=_precision(attrs))
    top, experts = jax.lax.top_k(logits, int(attrs["top_k"]))
    if attrs.get("norm_topk", True):
        weights = jax.nn.softmax(top, axis=-1)
    else:
        weights = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1),
                                      experts, axis=-1)
    return rows, live, experts, weights


def _moe_counts(live, experts, e: int):
    """(1, E) int32: live rows routed to each expert."""
    hits = jnp.where(live[:, None], experts, e).reshape(-1)
    return jnp.bincount(hits, length=e + 1)[None, :e].astype(jnp.int32)


def _moe_ref_cost(specs, attrs):
    """Every expert for every row: E times the grouped form's products,
    every expert's weights and the (N, E, f) intermediates."""
    x, wg = specs[0], specs[3]
    e, d, f = wg.shape
    n = x.nelems // d
    return Cost(flops=2.0 * n * d * e + 6.0 * n * e * d * f,
                bytes=_bytes(specs) + 4.0 * 6.0 * n * e * f
                      + 4.0 * 2.0 * n * e * d)


@impl("moe_ffn", "ref", cost_fn=_moe_ref_cost,
      note="the oracle: every expert computed for every row, batched over "
           "the experts, the weights of the experts not chosen set to zero")
def _moe_ffn_ref(inputs, attrs):
    x, n_new, router, wg, wu, wd = inputs
    e = wg.shape[0]
    prec = _precision(attrs)
    rows, live, experts, weights = _moe_route(x, n_new, router, attrs)
    each = jnp.broadcast_to(rows, (e,) + rows.shape)            # (E, N, d)
    gate = jnp.einsum("end,edf->enf", each, wg, precision=prec)
    up = jnp.einsum("end,edf->enf", each, wu, precision=prec)
    out = jnp.einsum("enf,efd->end", jax.nn.silu(gate) * up, wd,
                     precision=prec)
    dense = jnp.sum(jax.nn.one_hot(experts, e, dtype=rows.dtype)
                    * weights[..., None], axis=1)               # (N, E)
    y = jnp.einsum("ne,end->nd", jnp.where(live[:, None], dense, 0.0), out,
                   precision=prec)
    return [y.reshape(x.shape).astype(x.dtype),
            _moe_counts(live, experts, e)]


# rows of one tile of the TPU's grouped matmul (the Mosaic kernel that
# jax.lax.ragged_dot lowers to): it computes a whole tile of rows for every
# expert it visits
_GROUP_TILE = 128


@impl("moe_ffn", "xla", cost_fn=_moe_cost,
      note="dropless grouped matmuls: (row, expert) pairs sorted by expert, "
           "each hit expert's weights read once (jax.lax.ragged_dot, a "
           "Mosaic kernel on the TPU), then unsorted and summed per row; "
           "with fewer rows than a tile and every expert likely hit, the "
           "oracle's every-expert form, which computes the rows there are")
def _moe_ffn_xla(inputs, attrs):
    x, n_new, router, wg, wu, wd = inputs
    e, k, n = wg.shape[0], int(attrs["top_k"]), x.shape[0] * x.shape[1]
    if n <= _GROUP_TILE and e <= n * k:
        # a decode step: the grouped kernel would compute a whole tile of
        # rows for each of the (nearly all) experts the rows hit; every
        # expert batched over the rows reads the same weights once and
        # computes only the rows (on a TPU v5e: 2.14 against 5.75 ms a
        # layer at mellum2's 16 rows)
        return _moe_ffn_ref(inputs, attrs)
    prec = _precision(attrs)
    rows, live, experts, weights = _moe_route(x, n_new, router, attrs)
    # pairs of dead rows go to expert e, past every group: sorted last
    pair_expert = jnp.where(live[:, None], experts, e).reshape(-1)
    order = jnp.argsort(pair_expert, stable=True)
    sizes = jnp.bincount(pair_expert, length=e + 1)[:e]
    grouped = jnp.take(rows, order // k, axis=0)               # (N*k, d)
    gate = jax.lax.ragged_dot(grouped, wg, sizes, precision=prec)
    up = jax.lax.ragged_dot(grouped, wu, sizes, precision=prec)
    down = jax.lax.ragged_dot(jax.nn.silu(gate) * up, wd, sizes,
                              precision=prec)                  # (N*k, d)
    # rows past the last group belong to no expert: zero, whatever the
    # grouped product left there
    scaled = jnp.where((pair_expert[order] < e)[:, None],
                       down * weights.reshape(-1)[order][:, None], 0.0)
    back = jnp.zeros_like(order).at[order].set(jnp.arange(n * k))
    y = jnp.take(scaled, back, axis=0).reshape(n, k, -1).sum(axis=1)
    return [y.reshape(x.shape).astype(x.dtype),
            sizes[None, :].astype(jnp.int32)]


def moe_ffn(x, n_new, router, w_gate, w_up, w_down, *, top_k: int,
            norm_topk: bool = True, precision: Optional[str] = None,
            backend: str = "ref"):
    return get_impl("moe_ffn", backend)(
        [x, n_new, router, w_gate, w_up, w_down],
        {"top_k": top_k, "norm_topk": norm_topk, "precision": precision})


# --------------------------------------------------------------------------- #
# last_new_row — inputs (x (B,T,D), n_new (B,)).  A prefill chunk's next
# token is read at each slot's last new row only, so the prefill graph
# takes that row before the final norm and the head: they then run on B
# rows, not B*T, and the Program returns (B, V) logits.
# --------------------------------------------------------------------------- #

def _last_new_row_shape(specs, attrs):
    x, n_new = specs
    if len(x.shape) != 3 or tuple(n_new.shape) != (x.shape[0],):
        raise ValueError(f"last_new_row wants x (B, T, D) and n_new (B,), "
                         f"got {x.shape} and {n_new.shape}")
    return [TensorSpec((x.shape[0], 1, x.shape[2]), x.dtype)]


def _last_new_row_cost(specs, attrs):
    # reads one row per slot and writes it; n_new is read once
    out = _last_new_row_shape(specs, attrs)[0]
    return Cost(flops=0.0, bytes=2.0 * out.nbytes + specs[1].nbytes)


defop("last_new_row", _last_new_row_shape, _last_new_row_cost,
      doc="each slot's last new row of a chunk: x (B,T,D), n_new (B,) -> "
          "(B,1,D), row max(n_new[b]-1, 0) of slot b")


@impl("last_new_row", "ref",
      note="one row gather per slot (exact); an idle slot (n_new == 0) "
           "gives its row 0")
def _last_new_row_ref(inputs, attrs):
    x, n_new = inputs
    idx = jnp.maximum(n_new.astype(jnp.int32) - 1, 0)
    return [jnp.take_along_axis(x, idx[:, None, None], axis=1)]


def last_new_row(x, n_new, *, backend: str = "ref"):
    return get_impl("last_new_row", backend)([x, n_new], {})[0]


# --------------------------------------------------------------------------- #
# Speculative-decoding op.  (The verify step needs none of its own: it is
# a prefill chunk of T = K+1 rows through chunk_attention /
# paged_chunk_attention, whose backends the policy picks at that shape.)
#
# ``greedy_token`` is the in-graph argmax that lets the DRAFT Program feed
# its own greedy output back as the next step's input token — the K-step
# autoregressive draft then runs as one compiled Program call instead of K
# dispatches.
# --------------------------------------------------------------------------- #

def _greedy_token_shape(specs, attrs):
    logits = specs[0]
    if len(logits.shape) != 2:
        raise ValueError(f"greedy_token wants (B, V) logits, got {logits.shape}")
    return [TensorSpec((logits.shape[0], 1), "int32")]


def _greedy_token_cost(specs, attrs):
    # stream the logits once; the output is negligible
    return Cost(flops=float(specs[0].nelems), bytes=_bytes(specs))


defop("greedy_token", _greedy_token_shape, _greedy_token_cost,
      doc="greedy sampling inside a graph: (B, V) logits -> (B, 1) int32 "
          "argmax token ids (ties break to the lowest id, matching "
          "np.argmax on the host)")


@impl("greedy_token", "ref",
      note="jnp.argmax over the vocab axis; ties break to the lowest id, "
           "bit-identical to the engine's host-side np.argmax")
def _greedy_token_ref(inputs, attrs):
    return [jnp.argmax(inputs[0], axis=-1, keepdims=True).astype(jnp.int32)]


def greedy_token(logits, *, backend: str = "ref", **kw):
    return get_impl("greedy_token", backend)([logits], kw)[0]


# --------------------------------------------------------------------------- #
# ``tp`` backends — tensor-parallel attention over the head dim via
# shard_map.  Heads are independent through the whole softmax, so each
# device runs the stock xla lowering on its head slice with NO inner
# collective and bit-identical arithmetic to the single-device run; the
# only collective is the (exact, pure-data-movement) all-gather handing
# the head-sharded output back to the replicated half of the Program.
# supports() requires the serving mesh context (see serving_mesh above)
# and whole GQA groups per device: tp must divide both Hq and Hk — a
# GQA-small model falls back to the replicated backends instead.
# --------------------------------------------------------------------------- #

_HS4 = P(None, None, "model", None)   # (B,T,H,D) / (B,S,H,D)
_HS3 = P(None, "model", None)         # decode q (B,H,D)
_POOL = P(None, None, "model")        # pool (N,P,Hk*D): whole heads each
_SS2 = P(None, "model")               # scale sidecar (N,Hk)
_REP = P()


def _tp_attn_supports(specs, attrs):
    """serving mesh active with a "model" axis of size tp > 1 dividing
    both Hq and Hk (whole GQA groups per device)"""
    mesh, tp = _tp_state()
    if mesh is None:
        return False
    q, k = specs[0], specs[1]
    hk = k.shape[2] if len(k.shape) == 4 else _pool_heads(q, k)
    return hk > 0 and q.shape[-2] % tp == 0 and hk % tp == 0


def _tp_cost_fn(op: str):
    base_cost, shape_fn = get_op(op).cost_fn, get_op(op).shape_fn

    def cost(specs, attrs):
        """op streaming cost plus the (tp-1)/tp all-gather returning the
        head-sharded output to the replicated Program (collectives.
        allgather_bytes)"""
        from repro.sharding.collectives import allgather_bytes
        _, tp = _tp_state()
        base = base_cost(specs, attrs)
        out = shape_fn(specs, attrs)[0]
        return Cost(flops=base.flops,
                    bytes=base.bytes + allgather_bytes(out.nbytes, tp))
    return cost


def _tp_call(local_fn, args, in_specs, out_spec):
    from repro.sharding.collectives import replicate
    mesh, _ = _tp_state()
    # the bodies are per-head-local closures over host scalars, which the
    # static replication (vma) check cannot see through, so it stays off;
    # the engine's token-identity tests verify the numerics end to end
    out = jax.shard_map(local_fn, mesh=mesh, in_specs=tuple(in_specs),
                        out_specs=out_spec, check_vma=False)(*args)
    return replicate(out, mesh)


_TP_NOTE = ("shard_map over heads on the serving mesh; per-device xla "
            "lowering, output all-gathered back to replicated")


@impl("chunk_attention", "tp", supports=_tp_attn_supports,
      cost_fn=_tp_cost_fn("chunk_attention"), note=_TP_NOTE)
def _chunk_attention_tp(inputs, attrs):
    q, k, v, start = inputs
    def local(q_, k_, v_, s_):
        return _chunk_attention_xla([q_, k_, v_, s_], attrs)[0]
    return [_tp_call(local, (q, k, v, start),
                     (_HS4, _HS4, _HS4, _REP), _HS4)]


@impl("decode_attention", "tp", supports=_tp_attn_supports,
      cost_fn=_tp_cost_fn("decode_attention"), note=_TP_NOTE)
def _decode_attention_tp(inputs, attrs):
    q, k, v, lengths = inputs
    def local(q_, k_, v_, l_):
        return _decode_attention_xla_dense(q_, k_, v_, l_, attrs)
    return [_tp_call(local, (q, k, v, lengths),
                     (_HS3, _HS4, _HS4, _REP), _HS3)]


@impl("paged_chunk_attention", "tp", supports=_tp_attn_supports,
      cost_fn=_tp_cost_fn("paged_chunk_attention"), note=_TP_NOTE)
def _paged_chunk_attention_tp(inputs, attrs):
    q, pk, pv, tables, start = inputs
    def local(q_, pk_, pv_, t_, s_):
        return _paged_chunk_attention_xla([q_, pk_, pv_, t_, s_], attrs)[0]
    return [_tp_call(local, (q, pk, pv, tables, start),
                     (_HS4, _POOL, _POOL, _REP, _REP), _HS4)]


@impl("paged_decode_attention", "tp", supports=_tp_attn_supports,
      cost_fn=_tp_cost_fn("paged_decode_attention"), note=_TP_NOTE)
def _paged_decode_attention_tp(inputs, attrs):
    q, pk, pv, tables, lengths = inputs
    def local(q_, pk_, pv_, t_, l_):
        return _paged_decode_attention_xla([q_, pk_, pv_, t_, l_], attrs)[0]
    return [_tp_call(local, (q, pk, pv, tables, lengths),
                     (_HS3, _POOL, _POOL, _REP, _REP), _HS3)]


@impl("paged_chunk_attention_q", "tp", supports=_tp_attn_supports,
      cost_fn=_tp_cost_fn("paged_chunk_attention_q"), note=_TP_NOTE)
def _paged_chunk_attention_q_tp(inputs, attrs):
    q, pk, ks, pv, vs, tables, start = inputs
    def local(q_, pk_, ks_, pv_, vs_, t_, s_):
        return _paged_chunk_attention_q_xla(
            [q_, pk_, ks_, pv_, vs_, t_, s_], attrs)[0]
    return [_tp_call(local, (q, pk, ks, pv, vs, tables, start),
                     (_HS4, _POOL, _SS2, _POOL, _SS2, _REP, _REP), _HS4)]


@impl("paged_decode_attention_q", "tp", supports=_tp_attn_supports,
      cost_fn=_tp_cost_fn("paged_decode_attention_q"), note=_TP_NOTE)
def _paged_decode_attention_q_tp(inputs, attrs):
    q, pk, ks, pv, vs, tables, lengths = inputs
    def local(q_, pk_, ks_, pv_, vs_, t_, l_):
        return _paged_decode_attention_q_xla(
            [q_, pk_, ks_, pv_, vs_, t_, l_], attrs)[0]
    return [_tp_call(local, (q, pk, ks, pv, vs, tables, lengths),
                     (_HS3, _POOL, _SS2, _POOL, _SS2, _REP, _REP), _HS3)]


# the ops whose ``tp`` backend the engine prefers when given a mesh
TP_ATTENTION_OPS = (
    "chunk_attention", "decode_attention", "paged_chunk_attention",
    "paged_decode_attention", "paged_chunk_attention_q",
    "paged_decode_attention_q")
