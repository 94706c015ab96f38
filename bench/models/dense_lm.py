"""Plain reference of the dense decoder that the serving engine builds.

One module per model family.  A configuration file names its family
(``"family": "dense_lm"``) and the harness finds this module by that
name.  The module gives the harness five things, all functions of the
configuration file's sizes:

* ``serving_config``: the sizes as the system under test takes them;
* ``init_params``: seeded weights, made on the device in one jitted call
  in the type they are served in, under the value names the engine reads;
* ``forward``: the plain reference, a full causal forward pass over one
  padded token row, in straightforward ``jax.numpy``;
* ``matmul_params`` / ``row_flops`` / ``decode_step_bytes``: the
  operations and bytes that a step needs, for the rooflines.

The layer equations (pre-norm, sequential residual, no position
encoding, SwiGLU, GQA by repeating each kv head over its group)::

    h  = rmsnorm(x) * norm1
    q, k, v = h Wq, h Wk, h Wv                 # heads of d_head
    a  = softmax(q k^T / sqrt(d_head) + causal) v
    x  = x + a Wo
    h  = rmsnorm(x) * norm2
    x  = x + (silu(h Wg) * (h Wu)) Wd
    logits = (rmsnorm(x) * final_norm) W_head

The reference imports nothing of the program under test.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Mapping

import jax
import jax.numpy as jnp
import numpy as np

F32 = 4  # bytes of a float32


def sizes(cfg: Mapping[str, Any]) -> Dict[str, int]:
    """The configuration file's sizes under short names."""
    d = int(cfg["hidden_size"])
    hq = int(cfg["num_attention_heads"])
    dh = int(cfg.get("head_dim") or d // hq)
    return {"d": d, "hq": hq, "hk": int(cfg["num_key_value_heads"]),
            "dh": dh, "ff": int(cfg["intermediate_size"]),
            "L": int(cfg["num_hidden_layers"]), "V": int(cfg["vocab_size"])}


def serving_config(cfg: Mapping[str, Any]) -> Dict[str, Any]:
    """Keyword arguments of the engine's model config for these sizes."""
    s = sizes(cfg)
    if s["dh"] * s["hq"] != s["d"]:
        raise ValueError(f"the engine takes d_head = hidden / heads; "
                         f"{s['d']} / {s['hq']} != {s['dh']}")
    return {"vocab": s["V"], "d_model": s["d"], "n_layers": s["L"],
            "n_heads": s["hq"], "n_kv_heads": s["hk"], "d_ff": s["ff"],
            "eps": eps(cfg)}


def eps(cfg: Mapping[str, Any]) -> float:
    """The normalisation epsilon, under either of its published keys."""
    return float(cfg.get("rms_norm_eps", cfg.get("layer_norm_eps")))


def _shapes(cfg: Mapping[str, Any]) -> Dict[str, tuple]:
    s = sizes(cfg)
    d, ff, V = s["d"], s["ff"], s["V"]
    out = {"embed": (V, d), "final_norm": (d,), "head_w": (d, V)}
    for i in range(s["L"]):
        out.update({f"l{i}.norm1": (d,), f"l{i}.wq": (d, s["hq"] * s["dh"]),
                    f"l{i}.wk": (d, s["hk"] * s["dh"]),
                    f"l{i}.wv": (d, s["hk"] * s["dh"]),
                    f"l{i}.wo": (s["hq"] * s["dh"], d), f"l{i}.norm2": (d,),
                    f"l{i}.wg": (d, ff), f"l{i}.wu": (d, ff),
                    f"l{i}.wd": (ff, d)})
    return out


def init_params(cfg: Mapping[str, Any], key: jax.Array) -> Dict[str, jax.Array]:
    """Weights from ``key`` in one jitted call, as float32 device arrays.

    Dense matrices are N(0, 1/fan_in), so every activation and the logits
    stay about N(0, 1); the embedding is N(0, 0.25); norm scales are 1."""
    shapes = _shapes(cfg)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(shapes))
        out = {}
        for k, (name, shape) in zip(keys, sorted(shapes.items())):
            if len(shape) == 1:
                out[name] = jnp.ones(shape, jnp.float32)
            elif name == "embed":
                out[name] = 0.5 * jax.random.normal(k, shape, jnp.float32)
            else:
                out[name] = (jax.random.normal(k, shape, jnp.float32)
                             / math.sqrt(shape[0]))
        return out

    return make(key)


# --------------------------------------------------------------------------- #
# the plain reference
# --------------------------------------------------------------------------- #

def _rmsnorm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


@functools.partial(jax.jit, static_argnames=("hq", "hk", "eps"))
def _layer(x, w, *, hq, hk, eps):
    t, d = x.shape
    dh = w["wq"].shape[1] // hq
    h = _rmsnorm(x, w["norm1"], eps)
    q = (h @ w["wq"]).reshape(t, hq, dh)
    k = jnp.repeat((h @ w["wk"]).reshape(t, hk, dh), hq // hk, axis=1)
    v = jnp.repeat((h @ w["wv"]).reshape(t, hk, dh), hq // hk, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(
        jnp.asarray(dh, x.dtype))
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    scores = jnp.where(causal[None], scores, jnp.finfo(scores.dtype).min)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + a.reshape(t, hq * dh) @ w["wo"]
    h = _rmsnorm(x, w["norm2"], eps)
    return x + (jax.nn.silu(h @ w["wg"]) * (h @ w["wu"])) @ w["wd"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, head_w, *, eps):
    return _rmsnorm(x, norm, eps) @ head_w


def forward(cfg: Mapping[str, Any], params: Mapping[str, jax.Array],
            tokens: jax.Array, *, dtype=jnp.float32) -> jax.Array:
    """Logits (T, V) of a causal forward pass over ``tokens`` (T,).

    Run layer by layer, so that only one layer's activations live at a
    time.  ``dtype=float32`` computes at the configuration's own
    ``matmul_precision``; ``dtype=bfloat16`` casts weights and
    activations to bfloat16 throughout, the control one step below it.
    Rows after a position never change its logits, so a row padded at the
    end gives the same logits at the real positions."""
    s = sizes(cfg)
    e = eps(cfg)
    prec = cfg["matmul_precision"] if dtype == jnp.float32 else "default"
    cast = (lambda a: a) if dtype == jnp.float32 else (
        lambda a: a.astype(dtype))
    with jax.default_matmul_precision(prec):
        x = cast(params["embed"])[tokens]
        for i in range(s["L"]):
            w = {n: cast(params[f"l{i}.{n}"]) for n in
                 ("norm1", "wq", "wk", "wv", "wo", "norm2", "wg", "wu", "wd")}
            x = _layer(x, w, hq=s["hq"], hk=s["hk"], eps=e)
        return _head(x, cast(params["final_norm"]), cast(params["head_w"]),
                     eps=e)


# --------------------------------------------------------------------------- #
# operations and bytes
# --------------------------------------------------------------------------- #

def matmul_params(cfg: Mapping[str, Any]) -> int:
    """Weights that take part in a matrix product for every token: the
    layers' projections and the head (the embedding is a row lookup)."""
    s = sizes(cfg)
    d, dh = s["d"], s["dh"]
    per_layer = (d * s["hq"] * dh + 2 * d * s["hk"] * dh + s["hq"] * dh * d
                 + 3 * d * s["ff"])
    return s["L"] * per_layer + d * s["V"]


def row_flops(cfg: Mapping[str, Any], context: np.ndarray) -> float:
    """Operations to compute the rows whose key counts (position + 1,
    causal) are ``context``: 2 per matmul weight, plus q.k and p.v over
    each row's keys in every layer."""
    s = sizes(cfg)
    context = np.asarray(context, np.float64)
    attn = 4.0 * s["L"] * s["hq"] * s["dh"] * context
    return float(np.sum(2.0 * matmul_params(cfg) + attn))


def decode_step_bytes(cfg: Mapping[str, Any], lengths: np.ndarray) -> float:
    """Bytes a decode step must move at float32, for live slots whose
    caches hold ``lengths`` rows before the step: every weight read once,
    each slot's cached K/V rows read and its new row written, its
    embedding row read and its logits row written."""
    s = sizes(cfg)
    lengths = np.asarray(lengths, np.float64)
    row = 2.0 * s["L"] * s["hk"] * s["dh"] * F32
    weights = (matmul_params(cfg) + (2 * s["L"] + 1) * s["d"]) * F32
    per_slot = row * (lengths + 1) + s["d"] * F32 + s["V"] * F32
    return float(weights + np.sum(per_slot))


def kv_bytes_per_token(cfg: Mapping[str, Any]) -> int:
    s = sizes(cfg)
    return 2 * s["L"] * s["hk"] * s["dh"] * F32
