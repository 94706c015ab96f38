"""Kernel wrappers + registry integration — ties Pallas kernels into the
Orpheus backend registry.

This module (imported by ``import repro``):

1. declares the LM "macro ops" (attention, decode_attention, rmsnorm, ssd,
   moe_gemm, swiglu) with shape + analytic cost models,
2. registers their ``ref`` backends (the jnp oracles — differentiable, used
   by training and by the dry-run) and their ``pallas`` backends (the TPU
   kernels — the inference hot path, validated in interpret mode on CPU),
3. registers ``pallas`` backends for the existing graph ops ``conv2d`` /
   ``dense`` (im2col + MXU-tiled GEMM — the paper's GEMM convolution), and
4. exposes plain-function dispatchers (``attention(...)``,
   ``rmsnorm(...)``, …) used by :mod:`repro.layers`.

Pallas kernels execute via ``interpret=True`` automatically when the
default JAX backend is CPU (this container); on TPU they compile to Mosaic.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ir import TensorSpec
from repro.core.registry import Cost, defop, get_impl, impl
from repro.kernels import ref as R
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import flash_decode, flash_decode_partial
from repro.kernels.gemm import batched_gemm as _batched_gemm_kernel
from repro.kernels.gemm import gemm as _gemm_kernel
from repro.kernels.rmsnorm import rmsnorm as _rmsnorm_kernel
from repro.kernels.ssd import ssd_scan as _ssd_kernel

__all__ = [
    "attention", "decode_attention", "decode_attention_partial", "rmsnorm",
    "ssd", "ssd_step", "moe_gemm", "swiglu", "pallas_interpret",
]


def pallas_interpret() -> bool:
    """Interpret Pallas on CPU (this container); compile on TPU."""
    return jax.default_backend() == "cpu"


def _bytes(specs: Sequence[TensorSpec]) -> float:
    return float(sum(s.nbytes for s in specs))


# --------------------------------------------------------------------------- #
# attention (prefill / training forward)
# inputs: q (B,Sq,Hq,D), k (B,Skv,Hkv,D), v — attrs: causal, window, scale
# --------------------------------------------------------------------------- #

def _attn_shape(specs, attrs):
    q = specs[0]
    return [q]


def _attn_cost(specs, attrs):
    q, k = specs[0], specs[1]
    b, sq, hq, d = q.shape
    skv = k.shape[1]
    causal_frac = 0.5 if attrs.get("causal", True) and sq == skv else 1.0
    if attrs.get("window") and attrs["window"] < skv:
        causal_frac = min(causal_frac, attrs["window"] / skv)
    flops = 4.0 * b * hq * sq * skv * d * causal_frac
    out_b = q.nbytes
    return Cost(flops=flops, bytes=_bytes(specs) + out_b)


defop("attention", _attn_shape, _attn_cost,
      doc="GQA flash-style attention; attrs: causal, window, scale")


@impl("attention", "ref")
def _attention_ref_impl(inputs, attrs):
    q, k, v = inputs
    return [R.attention_ref(q, k, v, causal=attrs.get("causal", True),
                            window=attrs.get("window"),
                            scale=attrs.get("scale"))]


def _attn_pallas_supports(specs, attrs):
    q, k = specs[0], specs[1]
    bq = min(int(attrs.get("block_q", 256)), q.shape[1])
    bkv = min(int(attrs.get("block_kv", 512)), k.shape[1])
    return q.shape[1] % bq == 0 and k.shape[1] % bkv == 0


@impl("attention", "pallas", supports=_attn_pallas_supports,
      note="blockwise online-softmax flash kernel; masked blocks skipped")
def _attention_pallas_impl(inputs, attrs):
    q, k, v = inputs
    return [flash_attention(
        q, k, v, causal=attrs.get("causal", True), window=attrs.get("window"),
        scale=attrs.get("scale"), block_q=int(attrs.get("block_q", 256)),
        block_kv=int(attrs.get("block_kv", 512)),
        interpret=attrs.get("interpret", pallas_interpret()))]


def attention(q, k, v, *, causal=True, window=None, scale=None,
              backend="ref", **kw):
    return get_impl("attention", backend)(
        [q, k, v], {"causal": causal, "window": window, "scale": scale, **kw})[0]


# --------------------------------------------------------------------------- #
# decode_attention — one token vs KV cache
# inputs: q (B,Hq,D), k/v (B,Skv,Hkv,D), lengths (B,)
# --------------------------------------------------------------------------- #

def _dec_shape(specs, attrs):
    return [specs[0]]


def _dec_cost(specs, attrs):
    q, k = specs[0], specs[1]
    b, hq, d = q.shape
    skv = k.shape[1]
    # memory term dominates: whole cache streamed once
    return Cost(flops=4.0 * b * hq * skv * d,
                bytes=_bytes(specs) + q.nbytes)


defop("decode_attention", _dec_shape, _dec_cost,
      doc="single-token attention vs KV cache; inputs (q, k, v, lengths)")


@impl("decode_attention", "ref")
def _decode_ref_impl(inputs, attrs):
    q, k, v, lengths = inputs
    return [R.decode_attention_ref(q, k, v, lengths, scale=attrs.get("scale"))]


def _dec_pallas_supports(specs, attrs):
    """Skv % block_kv == 0 (block clamped to the cache length)."""
    k = specs[1]
    bkv = min(int(attrs.get("block_kv", 512)), k.shape[1])
    return k.shape[1] % bkv == 0


@impl("decode_attention", "pallas", supports=_dec_pallas_supports,
      note="streaming flash-decode; GQA group shares one KV read")
def _decode_pallas_impl(inputs, attrs):
    q, k, v, lengths = inputs
    return [flash_decode(q, k, v, lengths, scale=attrs.get("scale"),
                         block_kv=int(attrs.get("block_kv", 512)),
                         interpret=attrs.get("interpret", pallas_interpret()))]


def _dec_split_supports(specs, attrs):
    """n_splits >= 2 dividing Skv into >= 8-row shards, each shard a
    multiple of its (clamped) block_kv."""
    k = specs[1]
    n_splits = int(attrs.get("n_splits", 2))
    skv = k.shape[1]
    if n_splits < 2 or skv % n_splits or skv // n_splits < 8:
        return False
    part = skv // n_splits
    return part % min(int(attrs.get("block_kv", 512)), part) == 0


def _dec_split_cost(specs, attrs):
    """Adds the combine overhead: per-split (acc, m, l) partials written
    then re-read by the exact merge."""
    q = specs[0]
    n_splits = int(attrs.get("n_splits", 2))
    base = _dec_cost(specs, attrs)
    partials = n_splits * (q.nbytes + 8.0 * q.shape[0] * q.shape[1])
    return Cost(flops=base.flops, bytes=base.bytes + 2.0 * partials)


@impl("decode_attention", "pallas_split", supports=_dec_split_supports,
      cost_fn=_dec_split_cost,
      note="split-KV flash-decode for long caches: per-shard partials via "
           "flash_decode_partial, combined exactly (ref.combine_partials_ref)")
def _decode_split_impl(inputs, attrs):
    q, k, v, lengths = inputs
    n_splits = int(attrs.get("n_splits", 2))
    skv = k.shape[1]
    part = skv // n_splits
    if lengths is None:
        lengths = jnp.full((q.shape[0],), skv, jnp.int32)
    outs, ms, ls = [], [], []
    for i in range(n_splits):
        ks = jax.lax.slice_in_dim(k, i * part, (i + 1) * part, axis=1)
        vs = jax.lax.slice_in_dim(v, i * part, (i + 1) * part, axis=1)
        len_i = jnp.clip(lengths - i * part, 0, part)
        o, m, l = flash_decode_partial(
            q, ks, vs, len_i, scale=attrs.get("scale"),
            block_kv=int(attrs.get("block_kv", 512)),
            interpret=attrs.get("interpret", pallas_interpret()))
        outs.append(o)
        ms.append(m)
        ls.append(l)
    combined = R.combine_partials_ref(
        jnp.stack(outs).astype(jnp.float32), jnp.stack(ms), jnp.stack(ls))
    return [combined.astype(q.dtype)]


def decode_attention(q, k, v, lengths=None, *, scale=None, backend="ref", **kw):
    return get_impl("decode_attention", backend)(
        [q, k, v, lengths], {"scale": scale, **kw})[0]


def decode_attention_partial(q, k, v, lengths=None, *, scale=None,
                             backend="pallas", **kw):
    """(acc, m, l) partials for cross-shard combination (tree decode)."""
    if backend == "pallas":
        return flash_decode_partial(
            q, k, v, lengths, scale=scale,
            block_kv=int(kw.get("block_kv", 512)),
            interpret=kw.get("interpret", pallas_interpret()))
    # ref partial: full softmax stats computed densely
    b, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    scale_ = (1.0 / math.sqrt(d)) if scale is None else scale
    kf = R._repeat_kv(k, hq).astype(jnp.float32)
    vf = R._repeat_kv(v, hq).astype(jnp.float32)
    s = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32) * scale_, kf)
    if lengths is not None:
        s = jnp.where(jnp.arange(skv)[None, None, :] < lengths[:, None, None],
                      s, R._NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhk,bkhd->bhd", p, vf).astype(q.dtype)
    return acc, m, l


# --------------------------------------------------------------------------- #
# rmsnorm — attrs: eps; inputs (x, w) or (x, w, residual)
# --------------------------------------------------------------------------- #

def _rms_shape(specs, attrs):
    return [specs[0]]


def _rms_cost(specs, attrs):
    x = specs[0]
    extra = specs[2].nbytes if len(specs) > 2 else 0
    return Cost(flops=3.0 * x.nelems, bytes=2.0 * x.nbytes + specs[1].nbytes + extra)


defop("rmsnorm", _rms_shape, _rms_cost,
      doc="RMSNorm with optional fused residual; inputs (x, w[, residual])")


@impl("rmsnorm", "ref")
def _rms_ref_impl(inputs, attrs):
    x, w = inputs[0], inputs[1]
    res = inputs[2] if len(inputs) > 2 else None
    return [R.rmsnorm_ref(x, w, eps=float(attrs.get("eps", 1e-6)), residual=res)]


@impl("rmsnorm", "pallas", note="single-pass fused residual+norm+scale")
def _rms_pallas_impl(inputs, attrs):
    x, w = inputs[0], inputs[1]
    res = inputs[2] if len(inputs) > 2 else None
    return [_rmsnorm_kernel(x, w, eps=float(attrs.get("eps", 1e-6)),
                            residual=res,
                            block_rows=int(attrs.get("block_rows", 256)),
                            interpret=attrs.get("interpret", pallas_interpret()))]


def rmsnorm(x, w, *, eps=1e-6, residual=None, backend="ref", **kw):
    inputs = [x, w] if residual is None else [x, w, residual]
    return get_impl("rmsnorm", backend)(inputs, {"eps": eps, **kw})[0]


# --------------------------------------------------------------------------- #
# ssd (Mamba2) — inputs (x, dt, A, B, C, D) -> (y, final_state)
# --------------------------------------------------------------------------- #

def _ssd_shape(specs, attrs):
    x, _, _, B = specs[0], specs[1], specs[2], specs[3]
    b, s, h, p = x.shape
    n = B.shape[3]
    return [x, TensorSpec((b, h, p, n), "float32")]


def _ssd_cost(specs, attrs):
    x, _, _, B = specs[0], specs[1], specs[2], specs[3]
    b, s, h, p = x.shape
    n = B.shape[3]
    q = int(attrs.get("chunk", 128))
    # intra: (Q,N)x(N,Q) + (Q,Q)x(Q,P); inter: (Q,N)x(N,P); state: (Q,P)x(Q,N)
    per_chunk = 2.0 * q * q * n + 2.0 * q * q * p + 4.0 * q * n * p
    flops = b * h * (s / q) * per_chunk
    return Cost(flops=flops, bytes=_bytes(specs) + x.nbytes)


defop("ssd", _ssd_shape, _ssd_cost,
      doc="Mamba2 SSD scan -> (y, final_state); attrs: chunk")


@impl("ssd", "ref", note="exact sequential recurrence (lax.scan)")
def _ssd_ref_impl(inputs, attrs):
    x, dt, A, B, C, D = inputs
    y, st = R.ssd_ref(x, dt, A, B, C, D)
    return [y, st]


def _ssd_pad_chunk(x, dt, B, C, q):
    """Pad seq to a chunk multiple with dt=0 steps — exactly state-preserving
    (decay exp(0·A)=1, contribution dt·x=0); padded outputs are discarded."""
    s = x.shape[1]
    pad = (-s) % q
    if pad == 0:
        return x, dt, B, C, s
    pad4 = ((0, 0), (0, pad), (0, 0), (0, 0))
    return (jnp.pad(x, pad4), jnp.pad(dt, ((0, 0), (0, pad), (0, 0))),
            jnp.pad(B, pad4), jnp.pad(C, pad4), s)


@impl("ssd", "chunked", note="chunked SSD in jnp (matmul-form; XLA-fused)")
def _ssd_chunked_impl(inputs, attrs):
    x, dt, A, B, C, D = inputs
    q = min(int(attrs.get("chunk", 128)), x.shape[1])
    xp, dtp, Bp, Cp, s = _ssd_pad_chunk(x, dt, B, C, q)
    y, st = R.ssd_chunked_ref(xp, dtp, A, Bp, Cp, None, chunk=q)
    y = y[:, :s]
    if D is not None:
        y = (y.astype(jnp.float32)
             + x.astype(jnp.float32) * D.astype(jnp.float32)[None, None, :, None]
             ).astype(x.dtype)
    return [y, st]


@impl("ssd", "pallas", note="chunked SSD kernel; state carried in VMEM across chunks")
def _ssd_pallas_impl(inputs, attrs):
    x, dt, A, B, C, D = inputs
    q = min(int(attrs.get("chunk", 128)), x.shape[1])
    xp, dtp, Bp, Cp, s = _ssd_pad_chunk(x, dt, B, C, q)
    y, st = _ssd_kernel(xp, dtp, A, Bp, Cp, None, chunk=q,
                        interpret=attrs.get("interpret", pallas_interpret()))
    y = y[:, :s]
    if D is not None:
        y = (y.astype(jnp.float32)
             + x.astype(jnp.float32) * D.astype(jnp.float32)[None, None, :, None]
             ).astype(x.dtype)
    return [y, st]


def ssd(x, dt, A, B, C, D=None, *, chunk=128, backend="ref", **kw):
    y, st = get_impl("ssd", backend)([x, dt, A, B, C, D], {"chunk": chunk, **kw})
    return y, st


def ssd_step(x, dt, A, B, C, D, state):
    """Single decode step (always jnp; O(1) work, no kernel needed)."""
    return R.ssd_step_ref(x, dt, A, B, C, D, state)


# --------------------------------------------------------------------------- #
# moe_gemm — (E, C, d) @ (E, d, f): expert GEMMs after dispatch
# --------------------------------------------------------------------------- #

def _moe_gemm_shape(specs, attrs):
    x, w = specs
    return [TensorSpec((x.shape[0], x.shape[1], w.shape[2]), x.dtype)]


def _moe_gemm_cost(specs, attrs):
    x, w = specs
    e, c, d = x.shape
    f = w.shape[2]
    out_b = e * c * f * np.dtype(x.dtype).itemsize
    return Cost(flops=2.0 * e * c * d * f, bytes=_bytes(specs) + out_b)


defop("moe_gemm", _moe_gemm_shape, _moe_gemm_cost,
      doc="batched expert GEMM (E,C,d)@(E,d,f)")


@impl("moe_gemm", "ref")
def _moe_gemm_ref_impl(inputs, attrs):
    return [R.batched_gemm_ref(*inputs)]


@impl("moe_gemm", "pallas", note="grid (E, M/bm, N/bn, K/bk) batched MXU GEMM")
def _moe_gemm_pallas_impl(inputs, attrs):
    x, w = inputs
    return [_batched_gemm_kernel(
        x, w, block_m=int(attrs.get("block_m", 256)),
        block_n=int(attrs.get("block_n", 256)),
        block_k=int(attrs.get("block_k", 512)),
        interpret=attrs.get("interpret", pallas_interpret()))]


def moe_gemm(x, w, *, backend="ref", **kw):
    return get_impl("moe_gemm", backend)([x, w], kw)[0]


# --------------------------------------------------------------------------- #
# swiglu — elementwise silu(gate) * up (XLA fuses this well; ref only)
# --------------------------------------------------------------------------- #

defop("swiglu", lambda s, a: [s[0]],
      lambda s, a: Cost(flops=5.0 * s[0].nelems, bytes=_bytes(s) + s[0].nbytes),
      doc="silu(gate) * up")


@impl("swiglu", "ref")
def _swiglu_ref_impl(inputs, attrs):
    return [R.swiglu_ref(*inputs)]


def swiglu(gate, up, *, backend="ref", **kw):
    return get_impl("swiglu", backend)([gate, up], kw)[0]


# --------------------------------------------------------------------------- #
# pallas backends for the graph ops (conv2d / dense) — the paper's GEMM conv
# --------------------------------------------------------------------------- #

from repro.core import nnops as _nnops  # noqa: E402  (op declarations)


def _conv_pallas_supports(specs, attrs):
    return int(attrs.get("groups", 1)) == 1


@impl("conv2d", "pallas", supports=_conv_pallas_supports,
      note="GEMM convolution: im2col + MXU-tiled Pallas GEMM")
def _conv2d_pallas_impl(inputs, attrs):
    x, w = inputs
    kh, kw_, ci, co = w.shape
    stride = _nnops._pair(attrs.get("stride", 1))
    dilation = _nnops._pair(attrs.get("dilation", 1))
    pads = _nnops._conv_pads(attrs.get("padding", "SAME"), x.shape[1:3],
                             (kh, kw_), stride, dilation)
    cols = _nnops._im2col(x, (kh, kw_), stride, pads, dilation)
    n, oh, ow, kk = cols.shape
    out = _gemm_kernel(cols.reshape(n * oh * ow, kk), w.reshape(kk, co),
                       interpret=attrs.get("interpret", pallas_interpret()))
    return [out.reshape(n, oh, ow, co)]


impl("conv2d_fused", "pallas",
     supports=lambda specs, attrs: _conv_pallas_supports(specs[:2], attrs),
     note="GEMM conv + bias + act (epilogue in jnp)")(
         lambda inputs, attrs: [_nnops._act(
             _conv2d_pallas_impl(inputs[:2], attrs)[0] + inputs[2],
             attrs.get("act", "none"))])


@impl("dense", "pallas",
      supports=lambda specs, attrs: "precision" not in attrs,
      note="MXU-tiled GEMM at the kernel's own precision (a node that "
           "states one is refused)")
def _dense_pallas_impl(inputs, attrs):
    x, w = inputs
    lead = x.shape[:-1]
    out = _gemm_kernel(x.reshape(-1, x.shape[-1]), w,
                       interpret=attrs.get("interpret", pallas_interpret()))
    return [out.reshape(*lead, w.shape[-1])]
