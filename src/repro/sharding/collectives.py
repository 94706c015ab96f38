"""Collective strategies built on shard_map — the distribution-level
"backends" of Orpheus-JAX (selectable like any op backend).

* ``tree_decode_attention`` — sequence-parallel decode: the KV cache is
  sharded along its length dim over the "data" axis (long_500k, batch=1);
  every shard runs flash-decode over its slice and emits unnormalised
  partials (acc, m, l); shards combine with pmax/psum — mathematically
  exact (see ``ref.combine_partials_ref``), turning a full-cache gather
  into two scalar-ish collectives + one (B, Hq, D) psum.

* ``ring_allgather_matmul`` — overlap demonstration: all-gather of the
  row-sharded activation interleaved with per-chunk matmul via
  ``collective_permute`` (the classic ring schedule that hides comm behind
  MXU work on TPU).

* ``replicate`` / ``allgather_bytes`` — the pieces the serving engine's
  tensor-parallel attention backends (``kernels/serving_ops.py``'s ``tp``
  impls) use beside ``jax.shard_map``: a with_sharding_constraint that
  forces an (exact) all-gather of the head-sharded attention output, and
  the cost-model accounting for that gather's traffic.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.ops import decode_attention_partial

__all__ = ["tree_decode_attention", "ring_allgather_matmul",
           "replicate", "allgather_bytes"]


def replicate(x: jax.Array, mesh: Mesh) -> jax.Array:
    """Constrain ``x`` to be fully replicated on ``mesh`` — an explicit
    all-gather point.  Pure data movement, so bitwise exact; this is how
    the ``tp`` attention backends hand their head-sharded output back to
    the replicated half of the Program (o_proj onward)."""
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, P()))


def allgather_bytes(nbytes: float, degree: int) -> float:
    """Traffic one device moves all-gathering an ``nbytes`` global array
    sharded ``degree`` ways: each device receives the (degree-1) shards it
    doesn't hold."""
    return float(nbytes) * (degree - 1) / max(degree, 1)


def tree_decode_attention(mesh: Mesh, q: jax.Array, k: jax.Array,
                          v: jax.Array, lengths: jax.Array, *,
                          scale: Optional[float] = None, axis: str = "data",
                          backend: str = "ref") -> jax.Array:
    """q (B,Hq,D) replicated; k/v (B,Skv,Hkv,D) sharded on dim 1 over
    ``axis``; lengths (B,) global valid counts. Returns (B,Hq,Dv)."""
    n = mesh.shape[axis]
    skv = k.shape[1]
    assert skv % n == 0, (skv, n)
    s_loc = skv // n

    def local(q_, k_, v_, lengths_):
        idx = jax.lax.axis_index(axis)
        offset = (idx * s_loc).astype(jnp.int32)
        local_len = jnp.clip(lengths_ - offset, 0, s_loc)
        acc, m, l = decode_attention_partial(q_, k_, v_, local_len,
                                             scale=scale, backend=backend)
        m_glob = jax.lax.pmax(m, axis)                     # (B,Hq)
        alpha = jnp.exp(m - m_glob)
        l_glob = jax.lax.psum(l * alpha, axis)
        acc_glob = jax.lax.psum(acc.astype(jnp.float32) * alpha[..., None], axis)
        return (acc_glob / jnp.maximum(l_glob, 1e-30)[..., None]).astype(q_.dtype)

    in_specs = (P(), P(None, axis, None, None), P(None, axis, None, None), P())
    return jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                         out_specs=P())(q, k, v, lengths)


def ring_allgather_matmul(mesh: Mesh, x: jax.Array, w: jax.Array, *,
                          axis: str = "model") -> jax.Array:
    """y = allgather(x, axis) @ w, with the gather pipelined against the
    matmul: at step t each device multiplies the chunk it currently holds
    while collective-permuting it to the next neighbour.

    x (M, K) sharded on dim 0 over ``axis`` -> every device needs all of x;
    w (K, N) replicated inside shard_map (caller shards as needed).
    """
    n = mesh.shape[axis]

    def local(x_loc, w_):
        m_loc = x_loc.shape[0]
        perm = [(i, (i + 1) % n) for i in range(n)]
        idx0 = jax.lax.axis_index(axis)

        def step(carry, t):
            chunk, acc = carry
            # chunk currently holds shard (idx0 - t) mod n
            part = jnp.dot(chunk, w_, preferred_element_type=jnp.float32)
            src = (idx0 - t) % n
            acc = jax.lax.dynamic_update_slice(
                acc, part[None], (src % n, 0, 0))
            chunk = jax.lax.ppermute(chunk, axis, perm)
            return (chunk, acc), None

        acc0 = jnp.zeros((n, m_loc, w_.shape[1]), jnp.float32)
        # the carry becomes device-varying after the first axis_index use;
        # mark the initial value varying so scan's carry types match
        acc0 = jax.lax.pcast(acc0, ("model",), to="varying")
        (chunk, acc), _ = jax.lax.scan(step, (x_loc, acc0), jnp.arange(n))
        return acc.reshape(n * m_loc, w_.shape[1]).astype(x_loc.dtype)

    # every device finishes holding the full (M, N) product, but the vma
    # type system sees an axis_index-dependent value and can't infer the
    # replication — disable the static check (numerics verified in tests).
    return jax.shard_map(local, mesh=mesh, in_specs=(P(axis, None), P()),
                         out_specs=P(), check_vma=False)(x, w)
