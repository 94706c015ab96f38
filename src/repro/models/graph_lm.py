"""A decoder-only transformer LM expressed as GraphIR — the serving
engine's model.

Unlike :class:`repro.models.lm.LM` (a Python class over layer functions),
this builder emits flat :class:`~repro.core.ir.Graph` objects, so the
prefill and decode steps go through the full staged compilation pipeline:
``compile(graph, policy=..., quantize=...)`` → :class:`Program`.  That is
the point of the serving engine — backend selection, quantization and the
autotune cache all apply to the serving hot path.

No node pins a backend: every op in these graphs — including the serving
ops ``embedding`` / ``cache_update`` / ``chunk_attention`` /
``decode_attention``, each of which carries ref/xla/pallas alternatives —
resolves through whatever :class:`~repro.core.selector.BackendPolicy` the
caller compiles with, and an :class:`~repro.core.selector.AutotunePolicy`
measures the candidates at the exact batch/chunk/cache-capacity shapes
these builders emit (persisted in the on-disk autotune cache).

State is functional: KV caches are graph *inputs* and *outputs*
(``cache_k{i}`` → ``new_cache_k{i}``), so a Program stays a pure function
and the engine threads cache arrays between calls.

Two graph shapes per model:

* decode:  tokens (B, 1)  — one token per slot, ``decode_attention`` hot op.
* prefill: tokens (B, T)  — one chunk per slot, ``chunk_attention``;
  ``n_new[b] <= T`` marks the valid prefix (0 = slot idle this step), so a
  fixed-shape Program serves ragged chunks and idle slots exactly.  Its
  logits are (B, V), of each slot's last new row only.

Value names are identical across batch/chunk variants of the same config,
which lets one calibration (``repro.core.quant.calibrate``) drive the
int8 quantization of every variant — the engine's batched Programs and
the unbatched reference then share activation scales and stay token-exact
against each other.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.core.ir import Graph, Node, TensorSpec

__all__ = ["GraphLMConfig", "init_lm_params", "refuse_beyond_dense",
           "build_decode_graph", "build_prefill_graph", "init_cache_inputs",
           "build_paged_decode_graph", "build_paged_prefill_graph",
           "init_paged_cache_inputs", "build_verify_graph",
           "build_paged_verify_graph", "build_paged_verify_seq_graph",
           "build_spec_commit_graph",
           "build_draft_graph", "expand_spec_ranges", "partition_roles"]


@dataclass(frozen=True)
class GraphLMConfig:
    """Shape of the graph LM.  GQA when ``n_kv_heads < n_heads``;
    ``d_head`` is ``head_dim`` when given, else ``d_model // n_heads``.

    ``windows`` gives each layer's attention window (0 or an empty tuple:
    full causal attention): a query at position i sees key j iff
    ``0 <= i - j < window``.  ``n_experts > 0`` replaces every layer's
    SwiGLU MLP by a dropless routed-expert FFN (``moe_ffn``): ``top_k`` of
    ``n_experts`` SwiGLU experts of width ``d_expert`` per token, weighted
    by the softmax of the router logits over the chosen ones
    (``norm_topk``) or over all experts.  ``matmul_precision``
    (``"highest"``, ...) is carried into every matmul node of the graphs;
    None leaves each op's own default."""

    vocab: int = 128
    d_model: int = 64
    n_layers: int = 2
    n_heads: int = 4
    n_kv_heads: int = 2
    d_ff: int = 128
    eps: float = 1e-6
    head_dim: Optional[int] = None
    windows: Tuple[int, ...] = ()
    n_experts: int = 0
    top_k: int = 0
    d_expert: int = 0
    norm_topk: bool = True
    matmul_precision: Optional[str] = None

    def __post_init__(self):
        if self.windows and len(self.windows) != self.n_layers:
            raise ValueError(f"{len(self.windows)} windows for "
                             f"{self.n_layers} layers")
        if self.n_experts and not 0 < self.top_k <= self.n_experts:
            raise ValueError(f"top_k {self.top_k} of {self.n_experts} "
                             f"experts")

    @property
    def d_head(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def window(self, layer: int) -> int:
        """Layer ``layer``'s attention window; 0 is full attention."""
        return self.windows[layer] if self.windows else 0

    @property
    def beyond_dense(self) -> List[str]:
        """What this config adds to the dense decoder: "windows",
        "experts"; the serving paths that have only the dense decoder
        (dense KV layout, int8, speculative decoding, TP) refuse these."""
        return ([n for n, on in (("windows", any(self.windows)),
                                 ("experts", self.n_experts > 0)) if on])


def init_lm_params(cfg: GraphLMConfig, seed: int = 0) -> Dict[str, np.ndarray]:
    """Deterministic random weights (numpy, float32), keyed by the value
    names the graph builders reference."""
    rng = np.random.default_rng(seed)

    def dense(*shape: int) -> np.ndarray:
        return (rng.standard_normal(shape)
                / np.sqrt(shape[-2])).astype(np.float32)

    dm, dh = cfg.d_model, cfg.d_head
    p: Dict[str, np.ndarray] = {
        "embed": (rng.standard_normal((cfg.vocab, dm)) * 0.5).astype(np.float32),
        "final_norm": np.ones((dm,), np.float32),
        "head_w": dense(dm, cfg.vocab),
    }
    for i in range(cfg.n_layers):
        p[f"l{i}.norm1"] = np.ones((dm,), np.float32)
        p[f"l{i}.wq"] = dense(dm, cfg.n_heads * dh)
        p[f"l{i}.wk"] = dense(dm, cfg.n_kv_heads * dh)
        p[f"l{i}.wv"] = dense(dm, cfg.n_kv_heads * dh)
        p[f"l{i}.wo"] = dense(cfg.n_heads * dh, dm)
        p[f"l{i}.norm2"] = np.ones((dm,), np.float32)
        if cfg.n_experts:
            e, f = cfg.n_experts, cfg.d_expert
            p[f"l{i}.router"] = dense(dm, e)
            p[f"l{i}.we_gate"] = dense(e, dm, f)
            p[f"l{i}.we_up"] = dense(e, dm, f)
            p[f"l{i}.we_down"] = dense(e, f, dm)
        else:
            p[f"l{i}.wg"] = dense(dm, cfg.d_ff)
            p[f"l{i}.wu"] = dense(dm, cfg.d_ff)
            p[f"l{i}.wd"] = dense(cfg.d_ff, dm)
    return p


def refuse_beyond_dense(cfg: GraphLMConfig, path: str) -> None:
    """Raise for a config with windows or experts on a serving path that
    has only the dense decoder (``path`` names it)."""
    if cfg.beyond_dense:
        raise ValueError(f"{path} serves the dense decoder only; this "
                         f"config has {' and '.join(cfg.beyond_dense)}")


def _cache_specs(cfg: GraphLMConfig, shape: Tuple[int, ...],
                 kv_dtype: str = "float32") -> Dict[str, TensorSpec]:
    """Cache input specs in graph order: each layer's K and V caches of
    ``shape`` and, for int8 pools, their (n_blocks, Hk) scale sidecars."""
    if kv_dtype not in ("float32", "int8"):
        raise ValueError(f"unsupported kv_dtype {kv_dtype!r}")
    out: Dict[str, TensorSpec] = {}
    for i in range(cfg.n_layers):
        out[f"cache_k{i}"] = out[f"cache_v{i}"] = TensorSpec(shape, kv_dtype)
        if kv_dtype == "int8":
            sspec = TensorSpec((shape[0], cfg.n_kv_heads), "float32")
            out[f"cache_k{i}_scale"] = out[f"cache_v{i}_scale"] = sspec
    return out


def _paged_shape(cfg: GraphLMConfig, n_blocks: int,
                 page_size: int) -> Tuple[int, int, int]:
    return (n_blocks, page_size, cfg.n_kv_heads * cfg.d_head)


def init_cache_inputs(cfg: GraphLMConfig, batch: int,
                      cache_cap: int) -> Dict[str, np.ndarray]:
    """Zeroed cache arrays matching the graph's cache input names."""
    specs = _cache_specs(cfg, (batch, cache_cap, cfg.n_kv_heads, cfg.d_head))
    return {n: np.zeros(s.shape, s.dtype) for n, s in specs.items()}


def init_paged_cache_inputs(cfg: GraphLMConfig, n_blocks: int,
                            page_size: int, *,
                            kv_dtype: str = "float32") -> Dict[str, np.ndarray]:
    """Zeroed page-pool arrays matching the paged graphs' cache input
    names.  Unlike the dense layout there is no batch dimension — one
    shared pool of ``n_blocks`` fixed-size pages per layer, indexed
    through per-sequence block tables, each page ``(page_size, Hk*D)``:
    head h holds lanes ``h*D .. h*D+D-1`` of a row, so the TPU keeps the
    pool page-major and updates it in place (see the paged ops in
    :mod:`repro.kernels.serving_ops`).  With ``kv_dtype="int8"`` the
    pools are int8 and each gains a ``cache_{k,v}{i}_scale`` sidecar
    ((n_blocks, Hk) float32, all zeros = every page empty)."""
    specs = _cache_specs(cfg, _paged_shape(cfg, n_blocks, page_size),
                         kv_dtype)
    return {n: np.zeros(s.shape, s.dtype) for n, s in specs.items()}


# --------------------------------------------------------------------------- #
# One decoder layer, for every serving graph.  A pass of the decoder stack
# (the one pass of a step graph, or one stage of an unrolled graph) names
# the values it shares across layers in a _Pass; a _KV flavour names the
# ops that write this pass's K/V rows into the cache and attend over it.
# --------------------------------------------------------------------------- #

@dataclass(frozen=True)
class _KV:
    """One KV-cache layout: its write op, its attention ops for a
    one-row decode pass and a T-row chunk, whether its pools are read
    through ``block_tables``, whether each pool has an int8 scale sidecar
    ``{pool}_scale``, and whether the attention node carries the layer's
    precision and window attrs (windows need the paged fp32 layout; the
    dense and int8 attention nodes run at their op's default precision)."""

    write: str
    decode: str
    chunk: str
    paged: bool
    scaled: bool
    attn_attrs: bool


_DENSE = _KV("cache_update", "decode_attention", "chunk_attention",
             paged=False, scaled=False, attn_attrs=False)
_PAGED = _KV("paged_cache_update", "paged_decode_attention",
             "paged_chunk_attention", paged=True, scaled=False,
             attn_attrs=True)
_PAGED_Q = _KV("paged_cache_update_q", "paged_decode_attention_q",
               "paged_chunk_attention_q", paged=True, scaled=True,
               attn_attrs=False)


@dataclass(frozen=True)
class _Pass:
    """The names one pass of the decoder stack shares across its layers:
    the value-name suffix, the ``start`` and ``n_new`` inputs, ``kvlen``
    (a one-row decode pass; None: a chunk, which attends from ``start``)
    and ``cache(pool)`` -> (input, output) name of a cache buffer."""

    sfx: str
    start: str
    n_new: str
    kvlen: Optional[str]
    cache: Callable[[str], Tuple[str, str]]


def _step_pass(decode: bool) -> _Pass:
    """The one pass of a step graph: cache ``c`` in, ``new_c`` out."""
    return _Pass("", "start", "n_new", "kvlen" if decode else None,
                 lambda c: (c, f"new_{c}"))


def _stage_pass(nodes: List[Node], j: int, n: int, n_new: str,
                final: Callable[[str], str] = lambda c: f"new_{c}") -> _Pass:
    """Stage ``j`` of ``n`` of an unrolled graph: its values carry
    ``.s{j}``; it starts one row after stage ``j - 1`` (the ``step_pos``
    node, appended here, adds the ``spec.one`` ones-vector param); it reads
    each cache buffer as the stage before left it and hands it on, the
    last stage's output named ``final(c)``."""
    sfx = f".s{j}"
    start = "start"
    if j:
        start = f"start{sfx}"
        nodes.append(Node(f"step_pos{sfx}", "add",
                          ["start" if j == 1 else f"start.s{j - 1}",
                           "spec.one"], [start]))

    def cache(c: str) -> Tuple[str, str]:
        return (c if j == 0 else f"{c}{sfx}",
                final(c) if j == n - 1 else f"{c}.s{j + 1}")
    return _Pass(sfx, start, n_new, f"kvlen{sfx}", cache)


def _buffers(kv: _KV, pool: str) -> List[str]:
    return [pool, f"{pool}_scale"] if kv.scaled else [pool]


def _kv_write(kv: _KV, name: str, pool: str, rows: str, ps: _Pass) -> Node:
    """Node ``name`` writing ``rows`` into cache ``pool`` (``cache_k0``,
    ...) at rows ``start .. start+n_new-1`` of each slot."""
    bufs = [ps.cache(b) for b in _buffers(kv, pool)]
    return Node(name, kv.write,
                [b[0] for b in bufs] + [rows]
                + (["block_tables"] if kv.paged else [])
                + [ps.start, ps.n_new],
                [b[1] for b in bufs])


def _kv_attend(kv: _KV, name: str, q: str, layer: int, ps: _Pass, out: str,
               attrs: Dict[str, Any]) -> Node:
    """Node ``name`` attending ``q`` over layer ``layer``'s written cache."""
    cache = [ps.cache(b)[1] for pool in (f"cache_k{layer}", f"cache_v{layer}")
             for b in _buffers(kv, pool)]
    return Node(name, kv.decode if ps.kvlen else kv.chunk,
                [q] + cache + (["block_tables"] if kv.paged else [])
                + [ps.kvlen or ps.start], [out],
                dict(attrs) if kv.attn_attrs else {})


def _precision(cfg: GraphLMConfig) -> Dict[str, Any]:
    """The attrs every node that multiplies matrices carries."""
    return ({"precision": cfg.matmul_precision} if cfg.matmul_precision
            else {})


def _layer(nodes: List[Node], cfg: GraphLMConfig, i: int, x: str, kv: _KV,
           ps: _Pass, *, batch: int, t: int) -> str:
    """Append decoder layer ``i`` over input ``x`` ((B, t, D) rows of pass
    ``ps``) and return the name of its output."""
    L, dh, hq, hk = f"l{i}", cfg.d_head, cfg.n_heads, cfg.n_kv_heads
    prec, eps = _precision(cfg), {"eps": cfg.eps}

    def v(name: str) -> str:
        return f"{L}.{name}{ps.sfx}"

    decode = ps.kvlen is not None
    q = v("qd" if decode else "q4")
    att = dict(prec, window=cfg.window(i)) if cfg.window(i) else prec
    nodes += [
        Node(v("attn_norm"), "rmsnorm", [x, f"{L}.norm1"], [v("h1")],
             dict(eps)),
        Node(v("q_proj"), "dense", [v("h1"), f"{L}.wq"], [v("q")],
             dict(prec)),
        Node(v("k_proj"), "dense", [v("h1"), f"{L}.wk"], [v("k")],
             dict(prec)),
        Node(v("v_proj"), "dense", [v("h1"), f"{L}.wv"], [v("v")],
             dict(prec)),
        Node(v("k_heads"), "reshape", [v("k")], [v("k4")],
             {"shape": (batch, t, hk, dh)}),
        Node(v("v_heads"), "reshape", [v("v")], [v("v4")],
             {"shape": (batch, t, hk, dh)}),
        _kv_write(kv, v("k_write"), f"cache_k{i}", v("k4"), ps),
        _kv_write(kv, v("v_write"), f"cache_v{i}", v("v4"), ps),
        Node(v("q_heads"), "reshape", [v("q")], [q],
             {"shape": (batch, hq, dh) if decode else (batch, t, hq, dh)}),
        _kv_attend(kv, v("attn"), q, i, ps, v("att"), att),
        Node(v("attn_flat"), "reshape", [v("att")], [v("attn2")],
             {"shape": (batch, t, hq * dh)}),
        Node(v("o_proj"), "dense", [v("attn2"), f"{L}.wo"], [v("proj")],
             dict(prec)),
        Node(v("attn_res"), "add", [x, v("proj")], [v("xa")]),
        Node(v("mlp_norm"), "rmsnorm", [v("xa"), f"{L}.norm2"], [v("h2")],
             dict(eps)),
    ]
    if cfg.n_experts:
        # rows past n_new (chunk padding, idle slots) are routed to no
        # expert, so the per-layer counts hold live rows only
        nodes.append(Node(
            v("experts"), "moe_ffn",
            [v("h2"), ps.n_new, f"{L}.router", f"{L}.we_gate",
             f"{L}.we_up", f"{L}.we_down"],
            [v("down"), v("moe_counts")],
            dict(prec, top_k=cfg.top_k, norm_topk=cfg.norm_topk)))
    else:
        nodes += [
            Node(v("gate_proj"), "dense", [v("h2"), f"{L}.wg"], [v("gate")],
                 dict(prec)),
            Node(v("up_proj"), "dense", [v("h2"), f"{L}.wu"], [v("up")],
                 dict(prec)),
            Node(v("swiglu"), "swiglu", [v("gate"), v("up")], [v("act")]),
            Node(v("down_proj"), "dense", [v("act"), f"{L}.wd"], [v("down")],
                 dict(prec)),
        ]
    nodes.append(Node(v("mlp_res"), "add", [v("xa"), v("down")], [v("out")]))
    return v("out")


def _stack(nodes: List[Node], cfg: GraphLMConfig, kv: _KV, ps: _Pass,
           tokens: str, *, batch: int, t: int) -> str:
    """Append the embedding, a decode pass's key count and every decoder
    layer over ``tokens`` ((B, t) ids); return the last layer's output."""
    nodes.append(Node(f"embed_lookup{ps.sfx}", "embedding",
                      [tokens, "embed"], [f"x0{ps.sfx}"], _precision(cfg)))
    if ps.kvlen:
        nodes.append(Node(f"kv_len{ps.sfx}", "add", [ps.start, ps.n_new],
                          [ps.kvlen]))
    x = f"x0{ps.sfx}"
    for i in range(cfg.n_layers):
        x = _layer(nodes, cfg, i, x, kv, ps, batch=batch, t=t)
    return x


def _head(nodes: List[Node], cfg: GraphLMConfig, x: str, *, batch: int,
          sfx: str = "", per_row: bool = False) -> None:
    """Append the final norm and the LM head over ``x``: ``logits{sfx}``
    is (B, V) from one row per slot, or with ``per_row`` (B, T, V)."""
    nodes.append(Node(f"final_norm_n{sfx}", "rmsnorm", [x, "final_norm"],
                      [f"final_h{sfx}"], {"eps": cfg.eps}))
    if per_row:
        nodes.append(Node(f"lm_head{sfx}", "dense",
                          [f"final_h{sfx}", "head_w"], [f"logits{sfx}"],
                          _precision(cfg)))
        return
    nodes += [
        Node(f"lm_head{sfx}", "dense", [f"final_h{sfx}", "head_w"],
             [f"logits3{sfx}"], _precision(cfg)),
        Node(f"logits_flat{sfx}", "reshape", [f"logits3{sfx}"],
             [f"logits{sfx}"], {"shape": (batch, cfg.vocab)}),
    ]


def _lm_graph(cfg: GraphLMConfig, params: Dict[str, Any], *, batch: int,
              t: int, cache_cap: int, decode: bool, verify: bool = False,
              paged: Optional[Tuple[int, int, int]] = None,
              kv_dtype: str = "float32") -> Graph:
    if t > cache_cap:
        raise ValueError(f"chunk {t} exceeds cache capacity {cache_cap}")
    kv8 = kv_dtype == "int8"
    if kv8 and paged is None:
        raise ValueError("kv_dtype='int8' requires the paged cache layout")
    if any(cfg.windows) and paged is None:
        raise ValueError("attention windows need the paged cache layout")
    if kv8 or verify:
        refuse_beyond_dense(cfg, "int8 KV" if kv8 else "the verify step")
    inputs: Dict[str, TensorSpec] = {
        "tokens": TensorSpec((batch, t), "int32"),
        "start": TensorSpec((batch,), "int32"),
        "n_new": TensorSpec((batch,), "int32"),
    }
    if paged is None:
        kv = _DENSE
        caches = _cache_specs(cfg, (batch, cache_cap, cfg.n_kv_heads,
                                    cfg.d_head), kv_dtype)
    else:
        n_blocks, page_size, max_pages = paged
        kv = _PAGED_Q if kv8 else _PAGED
        inputs["block_tables"] = TensorSpec((batch, max_pages), "int32")
        caches = _cache_specs(cfg, _paged_shape(cfg, n_blocks, page_size),
                              kv_dtype)
    inputs.update(caches)

    nodes: List[Node] = []
    x = _stack(nodes, cfg, kv, _step_pass(decode), "tokens",
               batch=batch, t=t)
    if not (decode or verify):
        # the engine reads a prefill chunk's next token at each slot's last
        # new row alone: norm and head run on that row, as in decode
        nodes.append(Node("last_row", "last_new_row", [x, "n_new"],
                          ["x_last"]))
        x = "x_last"
    _head(nodes, cfg, x, batch=batch, per_row=verify)
    outputs = ["logits"] + [f"new_{c}" for c in caches]
    if cfg.n_experts:
        # (L, E) int32: live rows routed to each expert of each layer
        nodes.append(Node("moe_counts_all", "concat",
                          [f"l{i}.moe_counts" for i in range(cfg.n_layers)],
                          ["moe_counts"], {"axis": 0}))
        outputs.append("moe_counts")
    mode = "decode" if decode else ("verify" if verify else "prefill")
    tag = ("paged_kv8_" if kv8 else "paged_") if paged is not None else ""
    g = Graph(name=f"graph_lm_{tag}{mode}_b{batch}_t{t}", inputs=inputs,
              outputs=outputs, nodes=nodes, params=dict(params))
    g.validate()
    return g


def build_decode_graph(cfg: GraphLMConfig, params: Dict[str, Any], *,
                       batch: int, cache_cap: int) -> Graph:
    """One decode step for a fixed batch of slots: tokens (B, 1) + caches
    -> next-token logits (B, V) + updated caches.  ``n_new[b]`` in {0, 1}
    gates the cache write, so idle slots are untouched."""
    return _lm_graph(cfg, params, batch=batch, t=1, cache_cap=cache_cap,
                     decode=True)


def build_prefill_graph(cfg: GraphLMConfig, params: Dict[str, Any], *,
                        batch: int, chunk: int, cache_cap: int) -> Graph:
    """One prefill chunk: tokens (B, T) at absolute positions
    ``start .. start+n_new-1`` -> logits (B, V) of each slot's last new
    row ``n_new[b] - 1`` (row 0 for an idle slot) + updated caches.
    Positions >= ``n_new[b]`` are padding (their cache rows are
    overwritten by the next chunk or the first decode)."""
    return _lm_graph(cfg, params, batch=batch, t=chunk, cache_cap=cache_cap,
                     decode=False)


def build_paged_decode_graph(cfg: GraphLMConfig, params: Dict[str, Any], *,
                             batch: int, n_blocks: int, page_size: int,
                             max_pages: int,
                             kv_dtype: str = "float32") -> Graph:
    """Paged decode step: the dense caches are replaced by one shared
    page pool per layer (``(n_blocks, page_size, Hk*D)``) plus an int32
    ``block_tables`` input ``(B, max_pages)`` mapping each slot's logical
    page to a physical block.  Every activation value name matches the
    dense variant, so one calibration drives int8 quantization of both
    (the paged ops themselves are not quantized — they move cache rows).

    ``kv_dtype="int8"`` swaps the pools to int8 with per-(page, kv-head)
    float32 scale sidecars (``cache_{k,v}{i}_scale`` inputs ->
    ``new_...`` outputs) and routes writes/attention through the
    ``*_q`` serving ops; activation value names are unchanged, so the
    same calibration still drives these variants."""
    return _lm_graph(cfg, params, batch=batch, t=1,
                     cache_cap=max_pages * page_size, decode=True,
                     paged=(n_blocks, page_size, max_pages),
                     kv_dtype=kv_dtype)


def build_paged_prefill_graph(cfg: GraphLMConfig, params: Dict[str, Any], *,
                              batch: int, chunk: int, n_blocks: int,
                              page_size: int, max_pages: int,
                              kv_dtype: str = "float32") -> Graph:
    """Paged prefill chunk — see :func:`build_paged_decode_graph` for the
    cache layout (and the ``kv_dtype`` knob); chunk semantics match
    :func:`build_prefill_graph`."""
    return _lm_graph(cfg, params, batch=batch, t=chunk,
                     cache_cap=max_pages * page_size, decode=False,
                     paged=(n_blocks, page_size, max_pages),
                     kv_dtype=kv_dtype)


def build_verify_graph(cfg: GraphLMConfig, params: Dict[str, Any], *,
                       batch: int, width: int, cache_cap: int) -> Graph:
    """Speculative-verify step: tokens (B, width) — the committed next
    token plus up to ``width - 1`` draft proposals per slot — scored
    against the dense cache in one call, returning per-position logits
    (B, width, V).  It is a prefill chunk of T = ``width`` rows
    (``n_new[b] <= width`` marks the valid prefix, 0 = idle) whose head
    runs on every row; the backend policy picks the chunk attention's
    backend at this shape.  Value names match the prefill variant exactly
    — one calibration drives both, which is what keeps int8 speculative
    decode token-exact."""
    return _lm_graph(cfg, params, batch=batch, t=width, cache_cap=cache_cap,
                     decode=False, verify=True)


def build_paged_verify_graph(cfg: GraphLMConfig, params: Dict[str, Any], *,
                             batch: int, width: int, n_blocks: int,
                             page_size: int, max_pages: int) -> Graph:
    """Paged fp32 speculative-verify step — see :func:`build_verify_graph`;
    cache layout as in :func:`build_paged_decode_graph`.  (The int8 pools'
    verify is :func:`build_paged_verify_seq_graph`.)"""
    return _lm_graph(cfg, params, batch=batch, t=width,
                     cache_cap=max_pages * page_size, decode=False,
                     verify=True, paged=(n_blocks, page_size, max_pages))


def build_paged_verify_seq_graph(cfg: GraphLMConfig, params: Dict[str, Any],
                                 *, batch: int, width: int, n_blocks: int,
                                 page_size: int, max_pages: int) -> Graph:
    """The kv8 engine's verify Program: ``width`` single-row decode stages
    unrolled into ONE graph, threading the int8 page state stage to stage.

    Why not the chunk-shaped :func:`build_paged_verify_graph` here?
    Quantize-on-write makes int8 page bytes HISTORY-dependent (scales
    ratchet up; a raise requantizes the page), so a batched verify cannot
    reproduce plain decode's numerics bit-for-bit — and near-tied argmax
    rows would then flip tokens vs a non-speculative run.  This variant
    IS plain decode, stage by stage: stage ``j`` embeds its own token
    input (``tokens.s{j}``), quantize-writes that row in-graph, and runs
    ``paged_decode_attention_q`` at exactly the decode shapes — the same
    layer the decode Program runs — so every stage's logits are
    bit-identical to the decode Program at the same position, dispatched
    once instead of ``width`` times.  The threaded page state is
    DISCARDED (it includes later-rejected rows); instead each stage's fp32
    ``k4``/``v4`` rows are returned so the spec-commit replay
    (:func:`build_spec_commit_graph`) can rebuild the accepted prefix of
    the very same write sequence against the live pages.

    Stage masks ``n_new.s{j}`` are 1 while ``j`` is inside the slot's fed
    width, else 0 (idle stage: no write, garbage logits, ignored); the
    ``spec.one`` ones-vector param advances ``start`` in-graph.

    Outputs: ``logits.s0 .. logits.s{width-1}`` then per stage, per
    layer, the fp32 ``l{i}.k4.s{j}`` / ``l{i}.v4.s{j}`` rows."""
    if width < 1:
        raise ValueError(f"width must be >= 1, got {width}")
    refuse_beyond_dense(cfg, "the kv8 verify step")
    inputs: Dict[str, TensorSpec] = {
        "start": TensorSpec((batch,), "int32"),
        "block_tables": TensorSpec((batch, max_pages), "int32"),
    }
    for j in range(width):
        inputs[f"tokens.s{j}"] = TensorSpec((batch, 1), "int32")
        inputs[f"n_new.s{j}"] = TensorSpec((batch,), "int32")
    inputs.update(_cache_specs(cfg, _paged_shape(cfg, n_blocks, page_size),
                               "int8"))
    p = dict(params)
    p["spec.one"] = np.ones((batch,), np.int32)
    nodes: List[Node] = []
    for j in range(width):
        ps = _stage_pass(nodes, j, width, f"n_new.s{j}",
                         final=lambda c: f"{c}.sout{width - 1}")
        x = _stack(nodes, cfg, _PAGED_Q, ps, f"tokens{ps.sfx}",
                   batch=batch, t=1)
        _head(nodes, cfg, x, batch=batch, sfx=ps.sfx)
    outputs = [f"logits.s{j}" for j in range(width)]
    for j in range(width):
        for i in range(cfg.n_layers):
            outputs += [f"l{i}.k4.s{j}", f"l{i}.v4.s{j}"]
    g = Graph(name=f"graph_lm_paged_kv8_verify_seq_b{batch}_t{width}",
              inputs=inputs, outputs=outputs, nodes=nodes, params=p)
    g.validate()
    return g


def build_spec_commit_graph(cfg: GraphLMConfig, *, batch: int, width: int,
                            n_blocks: int, page_size: int,
                            max_pages: int) -> Graph:
    """The kv8 spec-commit step: REPLAY the accepted prefix of the verify
    call's write sequence against the live int8 pages.

    The kv8 verify (:func:`build_paged_verify_seq_graph`) threads its
    quantize-on-write page state internally but that state includes
    later-rejected rows (whose scale raises would lossily requantize
    committed neighbours), so the engine discards it.  This graph takes
    the verify call's per-stage fp32 rows back (``k_new{i}.s{j}``,
    (B, 1, Hk, D)) and re-applies the SAME single-row
    ``paged_cache_update_q`` writes in the SAME order, with stage masks
    ``n_new.s{j}`` zeroed from the first rejected stage on — determinism
    makes the replayed page states bit-identical to the ones the verify
    attention actually read, which in turn are bit-identical to plain
    decode's write history.  No model weights; just the write chain."""
    hk, dh = cfg.n_kv_heads, cfg.d_head
    inputs: Dict[str, TensorSpec] = {
        "start": TensorSpec((batch,), "int32"),
        "block_tables": TensorSpec((batch, max_pages), "int32"),
    }
    for j in range(width):
        inputs[f"n_new.s{j}"] = TensorSpec((batch,), "int32")
        for i in range(cfg.n_layers):
            inputs[f"k_new{i}.s{j}"] = TensorSpec((batch, 1, hk, dh),
                                                  "float32")
            inputs[f"v_new{i}.s{j}"] = TensorSpec((batch, 1, hk, dh),
                                                  "float32")
    caches = _cache_specs(cfg, _paged_shape(cfg, n_blocks, page_size), "int8")
    inputs.update(caches)
    nodes: List[Node] = []
    for j in range(width):
        ps = _stage_pass(nodes, j, width, f"n_new.s{j}")
        for i in range(cfg.n_layers):
            nodes += [
                _kv_write(_PAGED_Q, f"l{i}.k_commit{ps.sfx}", f"cache_k{i}",
                          f"k_new{i}{ps.sfx}", ps),
                _kv_write(_PAGED_Q, f"l{i}.v_commit{ps.sfx}", f"cache_v{i}",
                          f"v_new{i}{ps.sfx}", ps),
            ]
    g = Graph(name=f"graph_lm_spec_commit_b{batch}_t{width}", inputs=inputs,
              outputs=[f"new_{c}" for c in caches], nodes=nodes,
              params={"spec.one": np.ones((batch,), np.int32)})
    g.validate()
    return g


def build_draft_graph(cfg: GraphLMConfig, params: Dict[str, Any], *,
                      batch: int, cache_cap: int, spec_k: int) -> Graph:
    """The draft Program: ``spec_k`` autoregressive greedy steps unrolled
    into ONE graph, plus a final cache-write-only step.

    At serving scale the draft model is dispatch-dominated, so K separate
    decode calls would eat the speculation win; instead the greedy
    feedback loop runs in-graph via the ``greedy_token`` op.  Step ``s``
    embeds its input token (step 0: the ``tokens`` input — the committed
    next token; step s>0: step s-1's ``draft_tok``), runs the decoder over
    the step's dense caches, and emits ``draft_tok.s{s}``.  Position
    arithmetic is in-graph too: a ``spec.one`` ones-vector param advances
    ``start`` / ``kvlen`` per step, so the host passes the same
    (tokens, start, n_new) triple as a plain decode call.

    The final step (``s == spec_k``) writes its input token's cache row
    but computes no logits: after a full accept the draft cache then
    already holds every committed row, so the next draft call needs no
    catch-up.  Rows written for later-rejected proposals are simply
    overwritten by the next call — the draft caches are private per-slot
    dense buffers (capacity ``cache_cap`` = committed cap + spec_k + 1)
    and never roll back.

    Value names carry a ``.s{s}`` suffix; :func:`expand_spec_ranges` maps
    a shared calibration onto them so the draft quantizes with the same
    static activation scales as every other variant.

    Outputs: ``draft_tok.s0 .. draft_tok.s{spec_k-1}`` then the usual
    ``new_cache_k{i}`` / ``new_cache_v{i}`` (from the final step)."""
    if spec_k < 1:
        raise ValueError(f"spec_k must be >= 1, got {spec_k}")
    if spec_k + 1 > cache_cap:
        raise ValueError(f"spec_k {spec_k} + 1 exceeds cache cap {cache_cap}")
    refuse_beyond_dense(cfg, "the draft model")
    inputs: Dict[str, TensorSpec] = {
        "tokens": TensorSpec((batch, 1), "int32"),
        "start": TensorSpec((batch,), "int32"),
        "n_new": TensorSpec((batch,), "int32"),
    }
    caches = _cache_specs(cfg, (batch, cache_cap, cfg.n_kv_heads, cfg.d_head))
    inputs.update(caches)
    p = dict(params)
    p["spec.one"] = np.ones((batch,), np.int32)
    nodes: List[Node] = []
    for s in range(spec_k + 1):
        ps = _stage_pass(nodes, s, spec_k + 1, "n_new")
        tok = "tokens" if s == 0 else f"draft_tok.s{s - 1}"
        x = _stack(nodes, cfg, _DENSE, ps, tok, batch=batch, t=1)
        if s < spec_k:
            _head(nodes, cfg, x, batch=batch, sfx=ps.sfx)
            nodes.append(Node(f"greedy{ps.sfx}", "greedy_token",
                              [f"logits{ps.sfx}"], [f"draft_tok{ps.sfx}"]))
    outputs = [f"draft_tok.s{s}" for s in range(spec_k)]
    outputs += [f"new_{c}" for c in caches]
    g = Graph(name=f"graph_lm_draft_b{batch}_k{spec_k}", inputs=inputs,
              outputs=outputs, nodes=nodes, params=p)
    g.validate()
    return g


def expand_spec_ranges(ranges: Dict[str, Any], spec_k: int) -> Dict[str, Any]:
    """Map a shared calibration onto the draft graph's step-suffixed value
    names: every base-name range is copied to ``<name>.s{0..spec_k}``.
    The draft's layers are a prefix of the target's, and its per-step
    activations are the same values the decode variant sees — so the
    expanded ranges give the quantized draft the same static activation
    scales as every other Program variant (names that stay unmatched fall
    back to the quantizer's dynamic per-batch scales, which is safe for
    the draft: its proposals are *checked*, never trusted)."""
    out = dict(ranges)
    for name, vr in ranges.items():
        for s in range(spec_k + 1):
            out[f"{name}.s{s}"] = vr
    return out


def partition_roles(graph: Graph) -> Dict[str, str]:
    """Serving-partition role of every value this graph exchanges with the
    engine: maps each graph input and output name to one of ``"col"``
    (column/head-parallel weight), ``"kv_col"`` (column-parallel iff the
    KV-head count divides the TP degree — GQA-small falls back to
    replication), ``"dense_cache"`` / ``"paged_pool"`` / ``"kv_scale"``
    (head-sharded serving state), or ``"replicated"``.

    Thin, mesh-free view over :func:`repro.sharding.specs.serving_value_role`
    — the single source of the rules the ``partition`` compile stage
    (``compile(graph, mesh=...)``) turns into concrete ``PartitionSpec``\\ s.
    Builders need no annotations because every value these graphs emit is
    named by role (``l{i}.wq``, ``cache_k{i}``, ``cache_k{i}_scale``,
    ``block_tables``, ``new_``-prefixed outputs), and this helper makes
    that implicit contract inspectable and testable.
    """
    from repro.core.pipeline import get_pass
    from repro.sharding.specs import serving_value_role

    if any(o not in graph.value_info and o not in graph.inputs
           for o in graph.outputs):
        graph = get_pass("infer_shapes")(graph)
    paged = "block_tables" in graph.inputs
    names = list(graph.inputs) + [o for o in graph.outputs
                                  if o not in graph.inputs]
    return {name: serving_value_role(name, graph.spec_of(name).shape,
                                     paged=paged)
            for name in names}
