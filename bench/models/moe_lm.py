"""Plain reference of the mixture-of-experts decoder with sliding-window
layers that the serving engine builds (Mellum2-style).

The harness finds this module by the configuration file's ``"family":
"moe_lm"`` and uses the same hooks as ``dense_lm.py``: ``serving_config``,
``init_params``, ``forward``, ``matmul_params`` / ``row_flops`` /
``decode_step_bytes`` / ``kv_bytes_per_token``, and ``sizes``.  The CPU
tests of the engine compare it with the program at a small size.

The layer equations (pre-norm, sequential residual, no position encoding,
GQA by repeating each kv head over its group, every MLP a routed-expert
SwiGLU)::

    h  = rmsnorm(x) * norm1
    q, k, v = h Wq, h Wk, h Wv                  # heads of head_dim
    a  = softmax(q k^T / sqrt(head_dim) + mask) v
         # query i sees key j iff 0 <= i - j, and on a sliding layer
         # also i - j < sliding_window
    x  = x + a Wo
    h  = rmsnorm(x) * norm2
    r  = h Wr;  S = the top_k indices of r (ties: the lower index)
    w_e = exp(r_e) / sum_{e' in S} exp(r_e')    # norm_topk_prob
    x  = x + sum_{e in S} w_e (silu(h Wg_e) * (h Wu_e)) Wd_e
    logits = (rmsnorm(x) * final_norm) W_head

Every expert is computed for every token and the weights of the experts
not chosen are zero: the plainest form, with no dispatch to get wrong.
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
SLIDING = "sliding_attention"


def sizes(cfg: Mapping[str, Any]) -> Dict[str, Any]:
    """The configuration file's sizes under short names; ``windows`` is
    each served layer's window (0: full attention)."""
    L = int(cfg["num_hidden_layers"])
    if any(t != "sparse" for t in cfg["mlp_layer_types"][:L]):
        raise ValueError("moe_lm serves layers whose MLP is sparse only")
    window = int(cfg["sliding_window"]) if cfg["use_sliding_window"] else 0
    return {"d": int(cfg["hidden_size"]),
            "hq": int(cfg["num_attention_heads"]),
            "hk": int(cfg["num_key_value_heads"]),
            "dh": int(cfg["head_dim"]), "L": L, "V": int(cfg["vocab_size"]),
            "E": int(cfg["num_experts"]), "k": int(cfg["num_experts_per_tok"]),
            "f": int(cfg["moe_intermediate_size"]),
            "norm_topk": bool(cfg["norm_topk_prob"]),
            "windows": tuple(window if t == SLIDING else 0
                             for t in cfg["layer_types"][:L])}


def eps(cfg: Mapping[str, Any]) -> float:
    return float(cfg["rms_norm_eps"])


def serving_config(cfg: Mapping[str, Any]) -> Dict[str, Any]:
    """Keyword arguments of the engine's model config for these sizes."""
    s = sizes(cfg)
    return {"vocab": s["V"], "d_model": s["d"], "n_layers": s["L"],
            "n_heads": s["hq"], "n_kv_heads": s["hk"], "head_dim": s["dh"],
            "eps": eps(cfg), "windows": s["windows"], "n_experts": s["E"],
            "top_k": s["k"], "d_expert": s["f"], "norm_topk": s["norm_topk"],
            "matmul_precision": cfg["matmul_precision"]}


def _shapes(cfg: Mapping[str, Any]) -> Dict[str, tuple]:
    s = sizes(cfg)
    d, dh, E, f, V = s["d"], s["dh"], s["E"], s["f"], s["V"]
    out = {"embed": (V, d), "final_norm": (d,), "head_w": (d, V)}
    for i in range(s["L"]):
        out.update({f"l{i}.norm1": (d,), f"l{i}.wq": (d, s["hq"] * dh),
                    f"l{i}.wk": (d, s["hk"] * dh),
                    f"l{i}.wv": (d, s["hk"] * dh),
                    f"l{i}.wo": (s["hq"] * dh, d), f"l{i}.norm2": (d,),
                    f"l{i}.router": (d, E), f"l{i}.we_gate": (E, d, f),
                    f"l{i}.we_up": (E, d, f), f"l{i}.we_down": (E, f, d)})
    return out


def init_params(cfg: Mapping[str, Any],
                key: jax.Array) -> Dict[str, jax.Array]:
    """Weights from ``key`` in one jitted call, as float32 device arrays.

    Matrices (each expert's and the router's too) are N(0, 1/fan_in), so
    every activation and the logits stay about N(0, 1); the embedding is
    N(0, 0.25); norm scales are 1."""
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
                             / math.sqrt(shape[-2]))
        return out

    return make(key)


# --------------------------------------------------------------------------- #
# the plain reference
# --------------------------------------------------------------------------- #

LAYER_WEIGHTS = ("norm1", "wq", "wk", "wv", "wo", "norm2", "router",
                 "we_gate", "we_up", "we_down")


def _rmsnorm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _experts(h, w, *, k, norm_topk):
    """sum over the top k experts of w_e * SwiGLU_e(h), computed for
    every expert with the weights of the others set to zero."""
    r = h @ w["router"]                                         # (T, E)
    top, chosen = jax.lax.top_k(r, k)
    if norm_topk:
        gate_w = jax.nn.softmax(top, axis=-1)
    else:
        gate_w = jnp.take_along_axis(jax.nn.softmax(r, axis=-1), chosen,
                                     axis=-1)
    weights = jnp.zeros_like(r).at[
        jnp.arange(r.shape[0])[:, None], chosen].set(gate_w)    # (T, E)
    gate = jnp.einsum("td,edf->tef", h, w["we_gate"])
    up = jnp.einsum("td,edf->tef", h, w["we_up"])
    out = jnp.einsum("tef,efd->ted", jax.nn.silu(gate) * up, w["we_down"])
    return jnp.einsum("te,ted->td", weights, out)


@functools.partial(jax.jit, static_argnames=("hq", "hk", "eps", "window",
                                             "k", "norm_topk"))
def _layer(x, w, *, hq, hk, eps, window, k, norm_topk):
    t, d = x.shape
    dh = w["wq"].shape[1] // hq
    h = _rmsnorm(x, w["norm1"], eps)
    q = (h @ w["wq"]).reshape(t, hq, dh)
    kk = jnp.repeat((h @ w["wk"]).reshape(t, hk, dh), hq // hk, axis=1)
    v = jnp.repeat((h @ w["wv"]).reshape(t, hk, dh), hq // hk, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, kk) / jnp.sqrt(
        jnp.asarray(dh, x.dtype))
    back = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]      # i - j
    seen = back >= 0
    if window:
        seen &= back < window
    scores = jnp.where(seen[None], scores, jnp.finfo(scores.dtype).min)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + a.reshape(t, hq * dh) @ w["wo"]
    h = _rmsnorm(x, w["norm2"], eps)
    return x + _experts(h, w, k=k, norm_topk=norm_topk)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, head_w, *, eps):
    return _rmsnorm(x, norm, eps) @ head_w


def forward(cfg: Mapping[str, Any], params: Mapping[str, jax.Array],
            tokens: jax.Array, *, dtype=jnp.float32) -> jax.Array:
    """Logits (T, V) of a causal forward pass over ``tokens`` (T,).

    Run layer by layer, so that only one layer's activations live at a
    time.  ``dtype=float32`` computes at the configuration's own
    ``matmul_precision``; ``dtype=bfloat16`` casts weights and
    activations to bfloat16 throughout (routing included), the control
    one step below it.  Rows after a position never change its logits,
    so a row padded at the end gives the same logits at the real
    positions."""
    s = sizes(cfg)
    e = eps(cfg)
    prec = cfg["matmul_precision"] if dtype == jnp.float32 else "default"
    cast = (lambda a: a) if dtype == jnp.float32 else (
        lambda a: a.astype(dtype))
    with jax.default_matmul_precision(prec):
        x = cast(params["embed"])[tokens]
        for i in range(s["L"]):
            w = {n: cast(params[f"l{i}.{n}"]) for n in LAYER_WEIGHTS}
            x = _layer(x, w, hq=s["hq"], hk=s["hk"], eps=e,
                       window=s["windows"][i], k=s["k"],
                       norm_topk=s["norm_topk"])
        return _head(x, cast(params["final_norm"]), cast(params["head_w"]),
                     eps=e)


# --------------------------------------------------------------------------- #
# operations and bytes
# --------------------------------------------------------------------------- #

def _attention_params(s) -> int:
    d, dh = s["d"], s["dh"]
    return d * s["hq"] * dh + 2 * d * s["hk"] * dh + s["hq"] * dh * d


def _expert_params(s) -> int:
    """Weights of one expert: gate, up and down."""
    return 3 * s["d"] * s["f"]


def matmul_params(cfg: Mapping[str, Any]) -> int:
    """Weights that take part in a matrix product for every token: the
    attention projections, the router, the top_k experts a token is
    routed to and the head (the embedding is a row lookup)."""
    s = sizes(cfg)
    per_layer = (_attention_params(s) + s["d"] * s["E"]
                 + s["k"] * _expert_params(s))
    return s["L"] * per_layer + s["d"] * s["V"]


def row_flops(cfg: Mapping[str, Any], context: np.ndarray) -> float:
    """Operations to compute the rows whose key counts (position + 1,
    causal) are ``context``: 2 per matmul weight a token uses, plus q.k
    and p.v over the keys each row sees in every layer (at most the
    window on a sliding layer)."""
    s = sizes(cfg)
    context = np.asarray(context, np.float64)
    keys = sum(np.minimum(context, w) if w else context
               for w in s["windows"])
    attn = 4.0 * s["hq"] * s["dh"] * keys
    return float(np.sum(2.0 * matmul_params(cfg) + attn))


def experts_hit(cfg: Mapping[str, Any], rows: int) -> float:
    """Expected distinct experts of one layer that ``rows`` rows hit,
    each routed to top_k of E uniformly: E (1 - (1 - k/E)^rows)."""
    s = sizes(cfg)
    return s["E"] * (1.0 - (1.0 - s["k"] / s["E"]) ** rows)


def decode_step_bytes(cfg: Mapping[str, Any], lengths: np.ndarray) -> float:
    """Bytes a decode step must move at float32, for live slots whose
    caches hold ``lengths`` rows before the step: the attention weights,
    routers, norms and head read once, each layer's experts that the live
    rows are expected to hit (uniform routing: the signature has no
    counts) read once, each slot's cached K/V rows that it sees (at most
    the window on a sliding layer) read and its new row written, its
    embedding row read and its logits row written."""
    s = sizes(cfg)
    lengths = np.asarray(lengths, np.float64)
    row = 2.0 * s["hk"] * s["dh"] * F32                  # K and V, one layer
    dense = (s["L"] * (_attention_params(s) + s["d"] * s["E"])
             + s["d"] * s["V"] + (2 * s["L"] + 1) * s["d"])
    experts = s["L"] * experts_hit(cfg, len(lengths)) * _expert_params(s)
    kv = sum(np.minimum(lengths + 1, w) if w else lengths + 1
             for w in s["windows"])
    per_slot = row * kv + s["d"] * F32 + s["V"] * F32
    return float((dense + experts) * F32 + np.sum(per_slot))


def kv_bytes_per_token(cfg: Mapping[str, Any]) -> int:
    s = sizes(cfg)
    return 2 * s["L"] * s["hk"] * s["dh"] * F32
