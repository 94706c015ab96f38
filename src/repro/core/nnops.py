"""Standard neural-network graph operators: declarations + ref/xla backends.

Layout conventions (TPU-native): activations NHWC, conv kernels HWIO.

Each op gets:
  * a shape function (used by ``passes.infer_shapes``),
  * an analytic cost model (used by the cost-model selector and the roofline
    tool when the op lowers to a Pallas custom call),
  * a ``ref`` backend — pure jnp, the oracle,
  * where meaningful, an ``xla`` backend — XLA's fused native lowering (the
    "third-party library" in Orpheus terms),
  * where meaningful, an alternative *algorithm* (e.g. ``winograd`` conv),
    mirroring the paper's GEMM-vs-spatial-pack comparison.

The ``ref`` conv2d IS the paper's GEMM (im2col) convolution, written in jnp;
``kernels/ops.py`` additionally registers the ``pallas`` TPU kernel version.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.ir import TensorSpec
from repro.core.registry import Cost, defop, impl

Attrs = Dict[str, Any]

# --------------------------------------------------------------------------- #
# Helpers
# --------------------------------------------------------------------------- #

def _pair(v) -> Tuple[int, int]:
    if isinstance(v, (tuple, list)):
        return int(v[0]), int(v[1])
    return int(v), int(v)


def _conv_pads(padding, in_hw, k_hw, stride, dilation) -> Tuple[Tuple[int, int], Tuple[int, int]]:
    """Resolve 'SAME'/'VALID'/explicit padding to ((ph0,ph1),(pw0,pw1))."""
    if isinstance(padding, str):
        pads = []
        for i in range(2):
            eff_k = (k_hw[i] - 1) * dilation[i] + 1
            if padding.upper() == "VALID":
                pads.append((0, 0))
            elif padding.upper() == "SAME":
                out = -(-in_hw[i] // stride[i])
                total = max((out - 1) * stride[i] + eff_k - in_hw[i], 0)
                pads.append((total // 2, total - total // 2))
            else:
                raise ValueError(f"bad padding {padding!r}")
        return tuple(pads)  # type: ignore[return-value]
    (a, b), (c, d) = padding
    return (int(a), int(b)), (int(c), int(d))


def _conv_out_hw(in_hw, k_hw, stride, pads, dilation) -> Tuple[int, int]:
    out = []
    for i in range(2):
        eff_k = (k_hw[i] - 1) * dilation[i] + 1
        out.append((in_hw[i] + pads[i][0] + pads[i][1] - eff_k) // stride[i] + 1)
    return out[0], out[1]


def _conv_geometry(specs: Sequence[TensorSpec], attrs: Attrs):
    x, w = specs[0], specs[1]
    n, h, wd, ci = x.shape
    kh, kw, ci_g, co = w.shape
    stride = _pair(attrs.get("stride", 1))
    dilation = _pair(attrs.get("dilation", 1))
    groups = int(attrs.get("groups", 1))
    pads = _conv_pads(attrs.get("padding", "SAME"), (h, wd), (kh, kw), stride, dilation)
    oh, ow = _conv_out_hw((h, wd), (kh, kw), stride, pads, dilation)
    return n, (h, wd), (kh, kw), ci, co, groups, stride, pads, dilation, (oh, ow)


def _act(x: jax.Array, name: str) -> jax.Array:
    if name in (None, "", "none", "identity", "linear"):
        return x
    if name == "relu":
        return jnp.maximum(x, 0)
    if name == "relu6":
        return jnp.clip(x, 0, 6)
    if name == "gelu":
        return jax.nn.gelu(x)
    if name == "silu":
        return jax.nn.silu(x)
    if name == "sigmoid":
        return jax.nn.sigmoid(x)
    if name == "tanh":
        return jnp.tanh(x)
    raise ValueError(f"unknown activation {name!r}")


def _bytes_of(specs: Sequence[TensorSpec]) -> float:
    return float(sum(s.nbytes for s in specs))


def _ew_shape(specs, attrs):
    return [specs[0]]


def _ew_cost(specs, attrs):
    out = specs[0]
    return Cost(flops=float(out.nelems), bytes=_bytes_of(specs) + out.nbytes)

# --------------------------------------------------------------------------- #
# conv2d  (inputs: x NHWC, w HWIO)   — the paper's flagship op
# --------------------------------------------------------------------------- #

def _conv2d_shape(specs, attrs):
    n, _, _, ci, co, groups, _, _, _, (oh, ow) = _conv_geometry(specs, attrs)
    kh, kw, ci_g, _ = specs[1].shape
    if ci_g * groups != ci:
        raise ValueError(f"conv2d channel mismatch: x has {ci}, w expects {ci_g}*{groups}")
    return [TensorSpec((n, oh, ow, co), specs[0].dtype)]


def _conv2d_cost(specs, attrs):
    n, _, (kh, kw), ci, co, groups, _, _, _, (oh, ow) = _conv_geometry(specs, attrs)
    flops = 2.0 * n * oh * ow * co * kh * kw * (ci // groups)
    out_bytes = n * oh * ow * co * np.dtype(specs[0].dtype).itemsize
    return Cost(flops=flops, bytes=_bytes_of(specs) + out_bytes)


defop("conv2d", _conv2d_shape, _conv2d_cost,
      doc="2-D convolution, NHWC x HWIO. attrs: stride, padding, dilation, groups")


def _im2col(x, k_hw, stride, pads, dilation):
    """Extract conv patches -> (N, OH, OW, KH*KW*CI). Pure jnp (GEMM conv)."""
    n, h, w, ci = x.shape
    kh, kw = k_hw
    x = jnp.pad(x, ((0, 0), pads[0], pads[1], (0, 0)))
    oh, ow = _conv_out_hw((h, w), (kh, kw), stride, pads, dilation)
    # Gather rows/cols by advanced indexing — compiles to gathers; fine for
    # the reference path (the Pallas kernel does this in VMEM tiles).
    i = (jnp.arange(oh)[:, None] * stride[0] + jnp.arange(kh)[None, :] * dilation[0])
    j = (jnp.arange(ow)[:, None] * stride[1] + jnp.arange(kw)[None, :] * dilation[1])
    # x: (N, Hp, Wp, C) -> (N, OH, KH, Wp, C) -> (N, OH, KH, OW, KW, C)
    patches = x[:, i, :, :]                    # (N, OH, KH, Wp, C)
    patches = patches[:, :, :, j, :]           # (N, OH, KH, OW, KW, C)
    patches = jnp.transpose(patches, (0, 1, 3, 2, 4, 5))  # (N, OH, OW, KH, KW, C)
    return patches.reshape(n, oh, ow, kh * kw * ci)


@impl("conv2d", "ref", note="GEMM (im2col) convolution in pure jnp — the paper's GEMM backend")
def _conv2d_ref(inputs, attrs):
    x, w = inputs
    kh, kw, ci_g, co = w.shape
    stride = _pair(attrs.get("stride", 1))
    dilation = _pair(attrs.get("dilation", 1))
    groups = int(attrs.get("groups", 1))
    pads = _conv_pads(attrs.get("padding", "SAME"), x.shape[1:3], (kh, kw), stride, dilation)
    if groups == 1:
        cols = _im2col(x, (kh, kw), stride, pads, dilation)
        out = jnp.einsum("nhwk,ko->nhwo", cols, w.reshape(kh * kw * ci_g, co),
                         preferred_element_type=x.dtype)
        return [out]
    # grouped: split channels, vmap the dense conv over the group axis
    n, h, wd, ci = x.shape
    xg = x.reshape(n, h, wd, groups, ci // groups)
    wg = w.reshape(kh, kw, ci_g, groups, co // groups)

    def one(xs, ws):  # xs: (N,H,W,cig), ws: (KH,KW,cig,cog)
        cols = _im2col(xs, (kh, kw), stride, pads, dilation)
        return jnp.einsum("nhwk,ko->nhwo", cols, ws.reshape(kh * kw * ci_g, -1),
                          preferred_element_type=x.dtype)

    out = jax.vmap(one, in_axes=(3, 3), out_axes=3)(xg, wg)  # (N,OH,OW,G,cog)
    return [out.reshape(out.shape[0], out.shape[1], out.shape[2], co)]


@impl("conv2d", "xla", note="XLA native direct convolution (lax.conv_general_dilated)")
def _conv2d_xla(inputs, attrs):
    x, w = inputs
    kh, kw, _, _ = w.shape
    stride = _pair(attrs.get("stride", 1))
    dilation = _pair(attrs.get("dilation", 1))
    groups = int(attrs.get("groups", 1))
    pads = _conv_pads(attrs.get("padding", "SAME"), x.shape[1:3], (kh, kw), stride, dilation)
    out = lax.conv_general_dilated(
        x, w, window_strides=stride, padding=pads, rhs_dilation=dilation,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups)
    return [out]


def _winograd_supported(specs, attrs):
    kh, kw, _, _ = specs[1].shape
    stride = _pair(attrs.get("stride", 1))
    dilation = _pair(attrs.get("dilation", 1))
    groups = int(attrs.get("groups", 1))
    return (kh, kw) == (3, 3) and stride == (1, 1) and dilation == (1, 1) and groups == 1


def _winograd_cost(specs, attrs):
    base = _conv2d_cost(specs, attrs)
    # F(2x2,3x3): 16 multiplies per 4 outputs vs 36 -> 4/9 of the MACs, plus
    # transform overhead ~ linear terms; model as flops * 4/9 and ~2x bytes
    # (transform-domain intermediates).
    return Cost(flops=base.flops * 4.0 / 9.0, bytes=base.bytes * 2.0)


@impl("conv2d", "winograd", supports=_winograd_supported, cost_fn=_winograd_cost,
      note="Winograd F(2x2,3x3): 2.25x fewer multiplies; 3x3 s1 only")
def _conv2d_winograd(inputs, attrs):
    """F(2x2, 3x3) Winograd. Transforms are fp32 for stability."""
    x, w = inputs
    dt = x.dtype
    kh, kw, ci, co = w.shape
    pads = _conv_pads(attrs.get("padding", "SAME"), x.shape[1:3], (3, 3), (1, 1), (1, 1))
    n, h, wd, _ = x.shape
    oh, ow = _conv_out_hw((h, wd), (3, 3), (1, 1), pads, (1, 1))
    # tile grid of 2x2 outputs, each needs a 4x4 input tile
    th, tw = -(-oh // 2), -(-ow // 2)
    # pad so that the tiled region covers everything
    Hp = 2 * th + 2
    Wp = 2 * tw + 2
    xp = jnp.pad(x, ((0, 0),
                     (pads[0][0], max(Hp - h - pads[0][0], 0)),
                     (pads[1][0], max(Wp - wd - pads[1][0], 0)),
                     (0, 0))).astype(jnp.float32)
    B = jnp.array([[1, 0, -1, 0], [0, 1, 1, 0], [0, -1, 1, 0], [0, 1, 0, -1]],
                  jnp.float32)
    G = jnp.array([[1, 0, 0], [0.5, 0.5, 0.5], [0.5, -0.5, 0.5], [0, 0, 1]],
                  jnp.float32)
    A = jnp.array([[1, 0], [1, 1], [1, -1], [0, -1]], jnp.float32)
    # kernel transform: (4,3)@(3,3)@(3,4) per (ci,co)
    wf = jnp.einsum("ab,bcio,cd->adio", G, w.astype(jnp.float32), G.T)  # (4,4,ci,co)
    # input tiles: (N, th, tw, 4, 4, ci)
    idx_h = (jnp.arange(th)[:, None] * 2 + jnp.arange(4)[None, :])
    idx_w = (jnp.arange(tw)[:, None] * 2 + jnp.arange(4)[None, :])
    tiles = xp[:, idx_h, :, :][:, :, :, idx_w, :]          # (N,th,4,tw,4,ci)
    tiles = jnp.transpose(tiles, (0, 1, 3, 2, 4, 5))       # (N,th,tw,4,4,ci)
    tf = jnp.einsum("ab,nxybci,cd->nxyadi", B, tiles, B.T)  # B @ tile @ B^T
    # elementwise multiply in transform domain + reduce ci
    m = jnp.einsum("nxyabi,abio->nxyabo", tf, wf)
    y = jnp.einsum("pa,nxyabo,bq->nxypqo", A.T, m, A)       # (N,th,tw,2,2,co)
    y = jnp.transpose(y, (0, 1, 3, 2, 4, 5)).reshape(n, 2 * th, 2 * tw, co)
    return [y[:, :oh, :ow, :].astype(dt)]

# --------------------------------------------------------------------------- #
# conv2d_fused = conv2d + bias + activation (created by the fusion pass)
# --------------------------------------------------------------------------- #

def _conv2d_fused_shape(specs, attrs):
    return _conv2d_shape(specs[:2], attrs)


def _conv2d_fused_cost(specs, attrs):
    base = _conv2d_cost(specs[:2], attrs)
    out = _conv2d_fused_shape(specs, attrs)[0]
    return Cost(flops=base.flops + 2.0 * out.nelems, bytes=base.bytes + specs[2].nbytes)


defop("conv2d_fused", _conv2d_fused_shape, _conv2d_fused_cost,
      doc="conv2d + bias + activation; inputs (x, w, b); attrs of conv2d + act")


def _fused_from(conv_backend):
    def fn(inputs, attrs):
        x, w, b = inputs
        (y,) = conv_backend([x, w], attrs)
        return [_act(y + b, attrs.get("act", "none"))]
    return fn


impl("conv2d_fused", "ref")(_fused_from(_conv2d_ref))
impl("conv2d_fused", "xla")(_fused_from(_conv2d_xla))
impl("conv2d_fused", "winograd",
     supports=lambda specs, attrs: _winograd_supported(specs[:2], attrs))(
         _fused_from(_conv2d_winograd))

# --------------------------------------------------------------------------- #
# dense / dense_fused
# --------------------------------------------------------------------------- #

def _dense_shape(specs, attrs):
    x, w = specs[0], specs[1]
    if x.shape[-1] != w.shape[0]:
        raise ValueError(f"dense mismatch {x.shape} x {w.shape}")
    return [TensorSpec(x.shape[:-1] + (w.shape[1],), x.dtype)]


def _dense_cost(specs, attrs):
    x, w = specs[0], specs[1]
    batch = x.nelems // x.shape[-1]
    flops = 2.0 * batch * w.shape[0] * w.shape[1]
    out_b = batch * w.shape[1] * np.dtype(x.dtype).itemsize
    return Cost(flops=flops, bytes=_bytes_of(specs) + out_b)


defop("dense", _dense_shape, _dense_cost, doc="x @ w")


@impl("dense", "ref")
def _dense_ref(inputs, attrs):
    x, w = inputs
    return [jnp.matmul(x, w, preferred_element_type=x.dtype,
                       precision=attrs.get("precision"))]


@impl("dense", "xla", note="lax.dot_general with fp32 accumulation, at the "
                           "node's ``precision`` attr where it has one")
def _dense_xla(inputs, attrs):
    x, w = inputs
    out = lax.dot_general(x, w, (((x.ndim - 1,), (0,)), ((), ())),
                          precision=attrs.get("precision"),
                          preferred_element_type=jnp.float32)
    return [out.astype(x.dtype)]


def _dense_fused_shape(specs, attrs):
    return _dense_shape(specs[:2], attrs)


def _dense_fused_cost(specs, attrs):
    base = _dense_cost(specs[:2], attrs)
    out = _dense_fused_shape(specs, attrs)[0]
    return Cost(base.flops + 2.0 * out.nelems, base.bytes + specs[2].nbytes)


defop("dense_fused", _dense_fused_shape, _dense_fused_cost,
      doc="dense + bias + activation; inputs (x, w, b)")


@impl("dense_fused", "ref")
def _dense_fused_ref(inputs, attrs):
    x, w, b = inputs
    (y,) = _dense_ref([x, w], attrs)
    return [_act(y + b, attrs.get("act", "none"))]

# --------------------------------------------------------------------------- #
# elementwise / activations
# --------------------------------------------------------------------------- #

def _binop_shape(specs, attrs):
    a, b = specs
    # numpy broadcast
    shape = np.broadcast_shapes(a.shape, b.shape)
    return [TensorSpec(tuple(int(d) for d in shape), a.dtype)]


defop("add", _binop_shape, _ew_cost)
defop("mul", _binop_shape, _ew_cost)


@impl("add", "ref")
def _add_ref(inputs, attrs):
    return [inputs[0] + inputs[1]]


@impl("mul", "ref")
def _mul_ref(inputs, attrs):
    return [inputs[0] * inputs[1]]


defop("bias_add", _binop_shape, _ew_cost, doc="x + b broadcast on last dim")


@impl("bias_add", "ref")
def _bias_add_ref(inputs, attrs):
    return [inputs[0] + inputs[1]]


for _name in ("relu", "relu6", "gelu", "silu", "sigmoid", "tanh", "identity"):
    defop(_name, _ew_shape, _ew_cost)

    def _mk(n):
        def fn(inputs, attrs):
            return [_act(inputs[0], n if n != "identity" else "none")]
        return fn

    impl(_name, "ref")(_mk(_name))


# fused_elementwise: a chain of unary elementwise ops collapsed into one node
# (created by passes.fuse_elementwise).  attrs["ops"] lists the stages in
# application order, e.g. ("relu", "tanh").

def _fused_ew_shape(specs, attrs):
    return [specs[0]]


def _fused_ew_cost(specs, attrs):
    # One read + one write for the whole chain — the fusion win vs. the sum
    # of the unfused stages (each of which round-trips the tensor).
    x = specs[0]
    n_stages = max(len(tuple(attrs.get("ops", ()))), 1)
    return Cost(flops=float(n_stages * x.nelems), bytes=2.0 * x.nbytes)


defop("fused_elementwise", _fused_ew_shape, _fused_ew_cost,
      doc="chain of unary elementwise ops; attrs: ops (tuple of op names)")


@impl("fused_elementwise", "ref",
      note="composes the ref impl of each stage — the oracle chain")
def _fused_ew_ref(inputs, attrs):
    from repro.core.registry import get_impl as _get_impl
    (x,) = inputs
    for op_name in tuple(attrs.get("ops", ())):
        (x,) = _get_impl(op_name, "ref")([x], {})
    return [x]


@impl("fused_elementwise", "xla",
      note="single traced composition — XLA fuses the chain into one loop")
def _fused_ew_xla(inputs, attrs):
    (x,) = inputs
    for op_name in tuple(attrs.get("ops", ())):
        x = _act(x, "none" if op_name == "identity" else op_name)
    return [x]


def _softmax_shape(specs, attrs):
    return [specs[0]]


defop("softmax", _softmax_shape,
      lambda specs, attrs: Cost(5.0 * specs[0].nelems, 2.0 * specs[0].nbytes))


@impl("softmax", "ref")
def _softmax_ref(inputs, attrs):
    return [jax.nn.softmax(inputs[0], axis=int(attrs.get("axis", -1)))]

# --------------------------------------------------------------------------- #
# pooling
# --------------------------------------------------------------------------- #

def _pool_shape(specs, attrs):
    x = specs[0]
    n, h, w, c = x.shape
    k = _pair(attrs.get("window", 2))
    s = _pair(attrs.get("stride", attrs.get("window", 2)))
    pads = _conv_pads(attrs.get("padding", "VALID"), (h, w), k, s, (1, 1))
    oh, ow = _conv_out_hw((h, w), k, s, pads, (1, 1))
    return [TensorSpec((n, oh, ow, c), x.dtype)]


def _pool_cost(specs, attrs):
    out = _pool_shape(specs, attrs)[0]
    k = _pair(attrs.get("window", 2))
    return Cost(flops=float(out.nelems * k[0] * k[1]),
                bytes=_bytes_of(specs) + out.nbytes)


defop("maxpool2d", _pool_shape, _pool_cost)
defop("avgpool2d", _pool_shape, _pool_cost)


def _pool(x, attrs, init, op, avg):
    k = _pair(attrs.get("window", 2))
    s = _pair(attrs.get("stride", attrs.get("window", 2)))
    pads = _conv_pads(attrs.get("padding", "VALID"), x.shape[1:3], k, s, (1, 1))
    y = lax.reduce_window(x, init, op, (1, k[0], k[1], 1), (1, s[0], s[1], 1),
                          ((0, 0), pads[0], pads[1], (0, 0)))
    if avg:
        y = y / (k[0] * k[1])
    return y


@impl("maxpool2d", "ref")
def _maxpool_ref(inputs, attrs):
    return [_pool(inputs[0], attrs, -jnp.inf, lax.max, avg=False)]


@impl("avgpool2d", "ref")
def _avgpool_ref(inputs, attrs):
    return [_pool(inputs[0], attrs, 0.0, lax.add, avg=True)]


def _gap_shape(specs, attrs):
    n, h, w, c = specs[0].shape
    return [TensorSpec((n, c), specs[0].dtype)]


defop("global_avgpool", _gap_shape,
      lambda specs, attrs: Cost(float(specs[0].nelems), specs[0].nbytes))


@impl("global_avgpool", "ref")
def _gap_ref(inputs, attrs):
    return [jnp.mean(inputs[0], axis=(1, 2))]

# --------------------------------------------------------------------------- #
# batchnorm (inference) — folds to scale/shift
# --------------------------------------------------------------------------- #

def _bn_shape(specs, attrs):
    return [specs[0]]


defop("batchnorm", _bn_shape,
      lambda specs, attrs: Cost(2.0 * specs[0].nelems, 2.0 * specs[0].nbytes),
      doc="inference BN; inputs (x, scale, bias, mean, var)")


@impl("batchnorm", "ref")
def _bn_ref(inputs, attrs):
    x, scale, bias, mean, var = inputs
    eps = float(attrs.get("eps", 1e-5))
    inv = scale * lax.rsqrt(var + eps)
    return [x * inv + (bias - mean * inv)]

# --------------------------------------------------------------------------- #
# shape plumbing
# --------------------------------------------------------------------------- #

def _flatten_shape(specs, attrs):
    x = specs[0]
    return [TensorSpec((x.shape[0], x.nelems // x.shape[0]), x.dtype)]


defop("flatten", _flatten_shape, lambda s, a: Cost(0.0, 0.0))


@impl("flatten", "ref")
def _flatten_ref(inputs, attrs):
    x = inputs[0]
    return [x.reshape(x.shape[0], -1)]


def _reshape_shape(specs, attrs):
    x = specs[0]
    shape = tuple(int(d) for d in attrs["shape"])
    if -1 in shape:
        known = -int(np.prod(shape))
        shape = tuple(d if d != -1 else x.nelems // known for d in shape)
    if int(np.prod(shape)) != x.nelems:
        raise ValueError(f"reshape {x.shape} -> {shape} size mismatch")
    return [TensorSpec(shape, x.dtype)]


defop("reshape", _reshape_shape, lambda s, a: Cost(0.0, 0.0))


@impl("reshape", "ref")
def _reshape_ref(inputs, attrs):
    return [inputs[0].reshape(tuple(int(d) for d in attrs["shape"]))]


def _transpose_shape(specs, attrs):
    x = specs[0]
    perm = tuple(int(d) for d in attrs["perm"])
    return [TensorSpec(tuple(x.shape[p] for p in perm), x.dtype)]


defop("transpose", _transpose_shape,
      lambda s, a: Cost(0.0, 2.0 * s[0].nbytes))


@impl("transpose", "ref")
def _transpose_ref(inputs, attrs):
    return [jnp.transpose(inputs[0], tuple(int(d) for d in attrs["perm"]))]


def _concat_shape(specs, attrs):
    axis = int(attrs.get("axis", -1))
    base = list(specs[0].shape)
    ax = axis % len(base)
    base[ax] = sum(s.shape[ax] for s in specs)
    return [TensorSpec(tuple(base), specs[0].dtype)]


defop("concat", _concat_shape,
      lambda s, a: Cost(0.0, 2.0 * sum(x.nbytes for x in s)))


@impl("concat", "ref")
def _concat_ref(inputs, attrs):
    return [jnp.concatenate(list(inputs), axis=int(attrs.get("axis", -1)))]
