"""Pure-jnp oracles for every Pallas kernel in this package.

Every function here is the ground truth its kernel is tested against
(``tests/test_kernels_*.py`` sweeps shapes/dtypes and asserts allclose).
They are also the ``ref`` backends registered in the op registry, and the
differentiable implementations used by training (`jax.grad` flows through
them; the Pallas kernels target the inference hot path — the paper is an
inference framework).

Shape conventions
-----------------
attention:        q (B, Sq, Hq, D), k/v (B, Skv, Hkv, D), Hq % Hkv == 0
decode_attention: q (B, Hq, D),     k/v (B, Skv, Hkv, D), lengths (B,)
ssd (mamba2):     x (B, S, H, P), dt (B, S, H), A (H,), B/C (B, S, G, N)
rmsnorm:          x (..., D), w (D,)
gemm:             x (M, K) @ w (K, N);  batched: (E, M, K) @ (E, K, N)
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "attention_ref", "decode_attention_ref", "combine_partials_ref",
    "ssd_ref", "ssd_chunked_ref", "ssd_step_ref",
    "rmsnorm_ref", "gemm_ref", "batched_gemm_ref", "swiglu_ref",
]

_NEG_INF = -1e30  # large-negative instead of -inf: keeps masked softmax NaN-free


def _repeat_kv(k: jax.Array, hq: int) -> jax.Array:
    """(B, S, Hkv, D) -> (B, S, Hq, D) by repeating each kv head."""
    hkv = k.shape[2]
    if hkv == hq:
        return k
    assert hq % hkv == 0, (hq, hkv)
    return jnp.repeat(k, hq // hkv, axis=2)


def attention_mask(sq: int, skv: int, *, causal: bool,
                   window: Optional[int] = None, offset: int = 0) -> jax.Array:
    """(Sq, Skv) boolean mask. ``offset`` is the absolute position of query
    row 0 minus key col 0 (for decode/chunked prefill: offset = skv - sq)."""
    row = jnp.arange(sq)[:, None] + offset
    col = jnp.arange(skv)[None, :]
    m = jnp.ones((sq, skv), dtype=bool)
    if causal:
        m &= col <= row
    if window is not None:
        m &= col > row - window
    return m


def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, window: Optional[int] = None,
                  scale: Optional[float] = None) -> jax.Array:
    """Full (training/prefill) attention with GQA, causal + sliding window."""
    b, sq, hq, d = q.shape
    skv = k.shape[1]
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    k = _repeat_kv(k, hq)
    v = _repeat_kv(v, hq)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    mask = attention_mask(sq, skv, causal=causal, window=window,
                          offset=skv - sq)
    s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def decode_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                         lengths: Optional[jax.Array] = None, *,
                         scale: Optional[float] = None,
                         window: Optional[int] = None,
                         precision=None) -> jax.Array:
    """One-new-token attention against a KV cache.

    q (B, Hq, D); k/v (B, Skv, Hkv, D); lengths (B,) int32 = #valid cache
    entries per sequence (the new token's own K/V already written at
    position lengths-1).  With ``window`` the new token sees only the
    last ``window`` entries; ``precision`` goes to both products."""
    b, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    k = _repeat_kv(k, hq)
    v = _repeat_kv(v, hq)
    s = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32), precision=precision)
    if lengths is not None:
        pos = jnp.arange(skv)[None, None, :]
        valid = pos < lengths[:, None, None]
        if window:
            valid &= pos >= lengths[:, None, None] - window
        s = jnp.where(valid, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhk,bkhd->bhd", p, v.astype(jnp.float32),
                   precision=precision)
    return o.astype(q.dtype)


def combine_partials_ref(outs: jax.Array, ms: jax.Array,
                         ls: jax.Array) -> jax.Array:
    """Combine flash partials over a leading 'split' axis.

    outs (S, ..., D) unnormalised accumulators, ms (S, ...) running max,
    ls (S, ...) running sum-of-exp. Returns the exact softmax-weighted
    output — the tree/sequence-parallel decode combiner."""
    m = jnp.max(ms, axis=0)
    alpha = jnp.exp(ms - m[None])          # (S, ...)
    l = jnp.sum(ls * alpha, axis=0)
    o = jnp.sum(outs * alpha[..., None], axis=0)
    return o / jnp.maximum(l, 1e-30)[..., None]


# --------------------------------------------------------------------------- #
# Mamba2 SSD
# --------------------------------------------------------------------------- #

def ssd_ref(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
            C: jax.Array, D: Optional[jax.Array] = None,
            init_state: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, jax.Array]:
    """Sequential state-space-duality recurrence (the exact oracle).

    x (B,S,H,P), dt (B,S,H), A (H,) negative, B/C (B,S,G,N) with H % G == 0.
    Returns y (B,S,H,P) and final state (B,H,P,N).

        a_t   = exp(dt_t * A)            (per head, scalar)
        S_t   = a_t S_{t-1} + (dt_t x_t) B_t^T   (P x N)
        y_t   = S_t C_t + D x_t
    """
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    hpg = h // g
    Bh = jnp.repeat(B, hpg, axis=2)  # (B,S,H,N)
    Ch = jnp.repeat(C, hpg, axis=2)
    a = jnp.exp(dt.astype(jnp.float32) * A.astype(jnp.float32)[None, None, :])
    xbar = (x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None])
    state0 = (jnp.zeros((b, h, p, n), jnp.float32) if init_state is None
              else init_state.astype(jnp.float32))

    def step(state, inp):
        a_t, xb_t, B_t, C_t = inp  # (B,H), (B,H,P), (B,H,N), (B,H,N)
        state = state * a_t[..., None, None] + xb_t[..., None] * B_t[:, :, None, :]
        y_t = jnp.einsum("bhpn,bhn->bhp", state, C_t)
        return state, y_t

    xs = (jnp.moveaxis(a, 1, 0), jnp.moveaxis(xbar, 1, 0),
          jnp.moveaxis(Bh.astype(jnp.float32), 1, 0),
          jnp.moveaxis(Ch.astype(jnp.float32), 1, 0))
    final, ys = jax.lax.scan(step, state0, xs)
    y = jnp.moveaxis(ys, 0, 1)  # (B,S,H,P)
    if D is not None:
        y = y + x.astype(jnp.float32) * D.astype(jnp.float32)[None, None, :, None]
    return y.astype(x.dtype), final


def ssd_step_ref(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                 C: jax.Array, D: Optional[jax.Array],
                 state: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Single decode step. x (B,H,P), dt (B,H), B/C (B,G,N), state (B,H,P,N).
    Returns (y (B,H,P), new_state)."""
    y, new_state = ssd_ref(x[:, None], dt[:, None], A, B[:, None], C[:, None],
                           D, init_state=state)
    return y[:, 0], new_state


def ssd_chunked_ref(x, dt, A, B, C, D=None, init_state=None, chunk: int = 64):
    """Chunked SSD in pure jnp — the algorithm the Pallas kernel implements
    (intra-chunk quadratic + inter-chunk state carry), kept here both as
    documentation and as a second oracle for the kernel's block math."""
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    assert s % chunk == 0, "pad sequence to a chunk multiple"
    nc = s // chunk
    hpg = h // g
    Bh = jnp.repeat(B, hpg, axis=2).astype(jnp.float32)
    Ch = jnp.repeat(C, hpg, axis=2).astype(jnp.float32)
    la = (dt.astype(jnp.float32) * A.astype(jnp.float32)[None, None, :])  # log a
    xbar = x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None]

    def reshape_c(t):  # (B,S,...) -> (nc, B, chunk, ...)
        return jnp.moveaxis(t.reshape(b, nc, chunk, *t.shape[2:]), 1, 0)

    las, xs, Bs, Cs = map(reshape_c, (la, xbar, Bh, Ch))
    state0 = (jnp.zeros((b, h, p, n), jnp.float32) if init_state is None
              else init_state.astype(jnp.float32))

    def chunk_step(state, inp):
        lac, xc, Bc, Cc = inp          # (B,chunk,H[,*])
        cs = jnp.cumsum(lac, axis=1)   # (B,chunk,H) inclusive logs
        # intra: y[i] = sum_{j<=i} exp(cs_i - cs_j) (C_i . B_j) xbar_j
        smat = jnp.einsum("bihn,bjhn->bhij", Cc, Bc)
        dec = cs[:, :, None, :] - cs[:, None, :, :]          # (B,i,j,H)
        mask = jnp.tril(jnp.ones((chunk, chunk), bool))
        L = jnp.where(mask[None, :, :, None], jnp.exp(dec), 0.0)
        y_intra = jnp.einsum("bhij,bjhp->bihp", smat * jnp.moveaxis(L, 3, 1), xc)
        # inter: y[i] += exp(cs_i) C_i . state
        y_inter = jnp.einsum("bihn,bhpn->bihp", Cc, state) * jnp.exp(cs)[..., None]
        # state update: S' = exp(cs_last) S + sum_j exp(cs_last - cs_j) xbar_j B_j^T
        w = jnp.exp(cs[:, -1:, :] - cs)                       # (B,chunk,H)
        state = (state * jnp.exp(cs[:, -1, :])[..., None, None]
                 + jnp.einsum("bjhp,bjhn->bhpn", xc * w[..., None], Bc))
        return state, y_intra + y_inter

    from repro.analysis import unrolling
    if unrolling():
        # analysis mode: scans hide their trip count from cost_analysis —
        # run the chunk loop as Python (numerics identical; tests assert)
        state, ys_l = state0, []
        for ci in range(nc):
            state, y_c = chunk_step(state, (las[ci], xs[ci], Bs[ci], Cs[ci]))
            ys_l.append(y_c)
        final, ys = state, jnp.stack(ys_l)
    else:
        final, ys = jax.lax.scan(chunk_step, state0, (las, xs, Bs, Cs))
    y = jnp.moveaxis(ys, 0, 1).reshape(b, s, h, p)
    if D is not None:
        y = y + x.astype(jnp.float32) * D.astype(jnp.float32)[None, None, :, None]
    return y.astype(x.dtype), final


# --------------------------------------------------------------------------- #
# RMSNorm / GEMM / SwiGLU
# --------------------------------------------------------------------------- #

def rmsnorm_ref(x: jax.Array, w: jax.Array, *, eps: float = 1e-6,
                residual: Optional[jax.Array] = None) -> jax.Array:
    """RMSNorm with optional fused residual add (norm(x + residual))."""
    xf = x.astype(jnp.float32)
    if residual is not None:
        xf = xf + residual.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)
    return y.astype(x.dtype)


def gemm_ref(x: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)


def batched_gemm_ref(x: jax.Array, w: jax.Array) -> jax.Array:
    """(E, M, K) @ (E, K, N) -> (E, M, N)."""
    return jnp.einsum("emk,ekn->emn", x, w,
                      preferred_element_type=jnp.float32).astype(x.dtype)


def swiglu_ref(gate: jax.Array, up: jax.Array) -> jax.Array:
    return (jax.nn.silu(gate.astype(jnp.float32))
            * up.astype(jnp.float32)).astype(gate.dtype)
