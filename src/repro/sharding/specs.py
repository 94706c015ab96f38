"""Per-architecture PartitionSpec rules (DP / TP / EP / SP).

``param_specs`` walks a params pytree by key-path and assigns a
PartitionSpec per leaf from name-based rules, guarded by divisibility
checks against the mesh (a dim that doesn't divide falls back to
replication — this is how gemma3's 4-head attention ends up replicated on
a 16-way model axis while its FFN and vocab still carry TP).

Megatron pattern for transformer blocks:
  wq/wk/wv, w_gate/w_up  column-parallel  P(None, "model")
  wo, w_down             row-parallel     P("model", None)
  embed                  P("model", None)  (vocab-sharded)
  lm_head                P(None, "model")
  MoE experts            P("model", None, None)  (expert-parallel)
  Mamba streams          wz/wx column over d_inner; wdt over H;
                         out_proj row; B/C streams replicated (G*N small)
  norms / biases / A_log / D  replicated

Batch/activation rules: batch dim over ("pod","data"); for batch==1
long-context decode the KV-cache sequence dim is sharded over "data"
instead (sequence parallelism — the tree-decode path).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig

__all__ = ["param_specs", "batch_specs", "cache_specs", "data_axes",
           "named_shardings", "opt_state_specs",
           "serving_value_role", "graph_partition_specs", "mesh_axes",
           "check_mesh_compat"]


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    """The data-parallel axes: ("pod","data") on multi-pod, ("data",)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def ambient_mesh() -> Optional[Mesh]:
    """The context-manager mesh active at trace time (None outside one).
    Lets layer code apply sharding constraints only when actually lowering
    for a mesh — CPU tests and 1-device paths stay constraint-free."""
    try:
        from jax._src import mesh as mesh_lib  # noqa: PLC0415
        pm = mesh_lib.thread_resources.env.physical_mesh
        return None if pm.empty else pm
    except Exception:  # pragma: no cover - private API drift
        return None


def constrain(x, spec: P):
    """with_sharding_constraint iff an ambient mesh exists (else no-op)."""
    mesh = ambient_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def _div(n: int, mesh: Mesh, axis: str) -> bool:
    return axis in mesh.axis_names and n % mesh.shape[axis] == 0 and n > 0


def _key_str(path) -> Tuple[str, ...]:
    out = []
    for k in path:
        if hasattr(k, "key"):
            out.append(str(k.key))
        elif hasattr(k, "idx"):
            out.append(str(k.idx))
        else:
            out.append(str(k))
    return tuple(out)


def _param_rule(names: Tuple[str, ...], shape: Tuple[int, ...],
                cfg: ArchConfig, mesh: Mesh) -> P:
    name = names[-1] if names else ""
    leaf = name
    nd = len(shape)

    def col() -> P:  # column-parallel (shard last dim)
        if _div(shape[-1], mesh, "model"):
            return P(*([None] * (nd - 1) + ["model"]))
        return P()

    def row() -> P:  # row-parallel (shard first dim)
        if _div(shape[0], mesh, "model"):
            return P(*(["model"] + [None] * (nd - 1)))
        return P()

    # --- embeddings ---
    if leaf == "embed":
        return row()          # vocab-sharded
    if leaf == "lm_head":
        return col()

    # --- attention (megatron) ---
    if leaf in ("wq", "w_gate", "w_up", "w_in", "wz", "wx", "wuk", "wuv"):
        return col()
    if leaf in ("wk", "wv"):
        # shard kv heads only if they divide; else replicate (GQA small-kv)
        if _div(cfg.n_kv_heads, mesh, "model"):
            return col()
        return P()
    if leaf in ("wo", "w_down", "w_out", "out_proj"):
        return row()
    if leaf == "wdt":
        return col()
    if leaf in ("wdkv", "wkpe", "wB", "wC", "fuse"):
        return col() if leaf == "fuse" else P()

    # --- MoE experts: expert-parallel on the expert dim ---
    if nd == 3 and leaf in ("w_gate", "w_up", "w_down"):  # (E, d, f)
        pass  # unreachable (handled above by name), kept for clarity
    if leaf == "router":
        return P()

    # --- mamba conv / scalars / norms ---
    if leaf.startswith("conv_x") or leaf == "conv_bx":
        return col() if _div(shape[-1], mesh, "model") else P()
    if leaf.startswith("conv_") or leaf.startswith("norm") or leaf in (
            "A_log", "D", "dt_bias", "final_norm", "enc_norm", "b", "bias"):
        return P()
    return P()


def _moe_aware_rule(names: Tuple[str, ...], shape: Tuple[int, ...],
                    cfg: ArchConfig, mesh: Mesh) -> P:
    """Expert tensors are 3-D (E, ·, ·): shard the expert dim (EP)."""
    leaf = names[-1] if names else ""
    if len(shape) == 3 and leaf in ("w_gate", "w_up", "w_down"):
        if _div(shape[0], mesh, "model"):
            return P("model", None, None)
        return P()
    return _param_rule(names, shape, cfg, mesh)


def param_specs(params_shape: Any, cfg: ArchConfig, mesh: Mesh) -> Any:
    """PartitionSpec pytree matching ``params_shape`` (from jax.eval_shape).
    Stacked period params have a leading n_periods axis -> spec gets an
    extra None."""
    def assign(path, leaf):
        names = _key_str(path)
        shape = tuple(leaf.shape)
        stacked = "period" in names or names[-1] in ("0", "1")
        # stacked period params: (n_periods, ...) and shared: (2, ...)
        lead = 0
        if "period" in names:
            lead = 1
        elif "shared" in names and "stack" in names:
            lead = 1
        core = shape[lead:]
        spec = _moe_aware_rule(names, core, cfg, mesh)
        return P(*([None] * lead + list(spec)))

    return jax.tree_util.tree_map_with_path(assign, params_shape)


def batch_specs(batch_shape: Any, mesh: Mesh) -> Any:
    """Shard the leading batch dim over ("pod","data") when divisible."""
    dp = data_axes(mesh)
    dp_size = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1

    def assign(path, leaf):
        shape = tuple(leaf.shape)
        if shape and shape[0] % dp_size == 0 and dp_size > 1:
            return P(dp, *([None] * (len(shape) - 1)))
        return P()

    return jax.tree_util.tree_map_with_path(assign, batch_shape)


def cache_specs(cache_shape: Any, cfg: ArchConfig, mesh: Mesh, batch: int,
                seq_shard_fallback: bool = True) -> Any:
    """Decode-cache sharding.  Batch dim over DP axes when divisible; for
    batch==1 (long-context) the sequence/capacity dim is sharded over
    "data" instead (sequence parallelism).  KV head dims shard on "model"
    when divisible.

    ``seq_shard_fallback`` (perf iteration 1, EXPERIMENTS.md §Perf): when a
    cache's kv-head dim does NOT divide the model axis (stablelm/pixtral
    kv=8 vs model=16, gemma3 kv=1, MLA's single latent "head"), the
    baseline replicated the cache across "model" — 16x the HBM footprint
    and an all-gather per decode step.  The fallback shards the cache
    LENGTH dim over "model" instead (sequence-parallel attention inside the
    TP group; XLA partitions the masked softmax with small psums)."""
    dp = data_axes(mesh)
    dp_size = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1

    def assign(path, leaf):
        names = _key_str(path)
        shape = tuple(leaf.shape)
        lead = 1 if "period" in names else 0   # stacked (n_periods, ...)
        core = list(shape[lead:])
        spec: list = [None] * len(core)
        leaf_name = names[-1]
        if leaf_name in ("pages_k", "pages_v") and len(core) == 3:
            spec[2] = _pool_axis(getattr(cfg, "n_kv_heads", 0), mesh)
            return P(*([None] * lead + spec))
        if leaf_name.endswith("_scale") and len(core) == 2:
            # (N_pages, Hk) dequant sidecar: mirrors its pool's head shard
            if _div(core[1], mesh, "model"):
                spec[1] = "model"
            return P(*([None] * lead + spec))
        # core[0] = batch
        if core and core[0] == batch and batch % dp_size == 0 and dp_size > 1:
            spec[0] = dp
        elif core and batch == 1 and len(core) >= 2:
            # sequence-parallel: shard the cache length dim over "data"
            if leaf_name in ("k", "v", "ckv", "kpe") and _div(core[1], mesh, "data"):
                spec[1] = "data"
        if leaf_name in ("k", "v") and len(core) == 4:
            if _div(core[2], mesh, "model"):
                spec[2] = "model"
            elif seq_shard_fallback and _div(core[1], mesh, "model") \
                    and spec[1] is None:
                spec[1] = "model"
        if leaf_name in ("ckv", "kpe") and len(core) == 3 \
                and seq_shard_fallback and spec[1] is None \
                and _div(core[1], mesh, "model"):
            spec[1] = "model"      # MLA latent cache: shard length over TP
        if leaf_name == "ssm" and len(core) == 4:
            if _div(core[1], mesh, "model"):
                spec[1] = "model"
        if leaf_name == "conv_x" and len(core) == 3:
            if _div(core[2], mesh, "model"):
                spec[2] = "model"
        return P(*([None] * lead + spec))

    return jax.tree_util.tree_map_with_path(assign, cache_shape)


def opt_state_specs(params_shape: Any, param_spec: Any, mesh: Mesh,
                    zero1: bool = True) -> Any:
    """Adam moment sharding.  With ZeRO-1 each moment additionally shards
    its largest not-yet-sharded dim over the "data" axis (when divisible):
    grads arrive DP-replicated, each DP shard updates its slice, and XLA
    all-gathers the fresh params — the ZeRO-1 pattern expressed purely as
    sharding annotations."""
    if not zero1 or "data" not in mesh.axis_names:
        return param_spec
    dsize = mesh.shape["data"]

    def widen(leaf, spec):
        shape = tuple(leaf.shape)
        entries = list(spec) + [None] * (len(shape) - len(spec))
        # pick the largest replicated dim divisible by the data axis
        best, best_dim = -1, -1
        for i, (n, s) in enumerate(zip(shape, entries)):
            if s is None and n % dsize == 0 and n > best:
                best, best_dim = n, i
        if best_dim >= 0 and best >= dsize:
            entries[best_dim] = "data"
        return P(*entries)

    return jax.tree_util.tree_map(widen, params_shape, param_spec,
                                  is_leaf=lambda x: isinstance(x, P))


def named_shardings(spec_tree: Any, mesh: Mesh) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


# --------------------------------------------------------------------- #
# Serving-graph partitioning — rules behind compile(mesh=...)'s
# `partition` pass.  Every Program input / param / output gets a
# PartitionSpec derived from its *name* and *shape*; divisibility guards
# fall back to replication (the GQA-small fallback), never crash.
# --------------------------------------------------------------------- #

# scalar/bookkeeping serving inputs that must stay replicated: token ids,
# write cursors, and block tables (host-computed int32 indices)
SERVING_REPLICATED = ("tokens", "start", "n_new", "kvlen", "block_tables")


def serving_value_role(name: str, shape: Tuple[int, ...], *,
                       paged: bool = False) -> str:
    """Classify one serving-graph value into a partition role.

    Roles: ``replicated`` (tokens, cursors, tables, norms, logits, and —
    deliberately — the row-parallel candidates wo/wd/embed/head_w, see
    below), ``col`` (column-parallel projection weight), ``kv_col``
    (column-parallel iff whole kv heads divide the model axis),
    ``dense_cache`` ((B, S, Hk, D) cache), ``paged_pool``
    ((N_pages, page, Hk*D) pool, lane-dense so the TPU keeps it
    page-major), ``kv_scale`` ((N_pages, Hk) sidecar).

    wo/wd (and embed/head_w) are kept replicated rather than row-parallel:
    a row-parallel matmul splits the contraction dim and combines partial
    products with a psum, whose float-addition order differs from the
    single-device reduction — that breaks the engine's token-identity
    guarantee.  The TP win on those layers is given up in exchange for
    bitwise-exact serving; the attention shard_map backends charge the
    resulting all-gather in their cost models instead.
    """
    base = name[4:] if name.startswith("new_") else name
    leaf = base.rsplit(".", 1)[-1]
    if base in SERVING_REPLICATED or base.startswith("tokens."):
        return "replicated"
    if base.startswith("cache_k") or base.startswith("cache_v"):
        if base.endswith("_scale"):
            return "kv_scale" if len(shape) == 2 else "replicated"
        if paged and len(shape) == 3:
            return "paged_pool"
        return "dense_cache" if len(shape) == 4 else "replicated"
    if leaf in ("wq", "wg", "wu"):
        return "col"
    if leaf in ("wk", "wv"):
        return "kv_col"
    return "replicated"


def _pool_axis(kv_heads: int, mesh: Mesh) -> Optional[str]:
    """Mesh axis of a paged pool's lane axis.  The pool is (N_pages, page,
    Hk*D), lane-dense so the TPU keeps it page-major; its rows are
    block-addressed through tables, so neither the pool dim nor the page
    dim can shard usefully.  The Hk*D axis carries TP when whole kv heads
    land on each device (tp | Hk); otherwise, or when Hk is unknown, the
    pool replicates (the GQA-small fallback, never a crash)."""
    return "model" if kv_heads and _div(kv_heads, mesh, "model") else None


def _graph_kv_heads(graph: Any) -> int:
    """kv-head count of a serving graph, read from its cache operands as
    the ops lay them out: dim 2 of a dense (B, S, Hk, D) cache, dim 1 of
    an (N_pages, Hk) scale sidecar, else dim 2 of the (B, T, Hk, D) rows
    that a paged write takes.  0 when none is found."""
    for n, ts in graph.inputs.items():
        if n.startswith("cache_k") and len(ts.shape) == 4:
            return int(ts.shape[2])
        if n.startswith("cache_k") and n.endswith("_scale"):
            return int(ts.shape[1])
    writes = [nd for nd in graph.nodes if nd.op == "paged_cache_update"]
    if not writes:
        return 0
    if not graph.value_info:
        from repro.core.passes import infer_shapes
        graph = infer_shapes(graph)
    rows = graph.value_info.get(writes[0].inputs[1]) \
        or graph.inputs.get(writes[0].inputs[1])
    return int(rows.shape[2]) if rows is not None and len(rows.shape) == 4 \
        else 0


def graph_partition_specs(graph: Any, mesh: Mesh) -> Dict[str, P]:
    """PartitionSpec for every input, param and output of a serving graph.

    Name/shape-driven (the convention of :mod:`repro.models.graph_lm`'s
    builders): caches and paged pools shard the kv-head dim on "model"
    when it divides, scale sidecars mirror their pool, q/gate/up
    projections go column-parallel, wk/wv go column-parallel only when
    whole kv heads land on each device (GQA-small fallback: replicate),
    everything else — tokens, cursors, block tables, norms, wo/wd, embed,
    head_w, logits — is replicated.  Outputs mirror the input they update
    (``new_<name>`` strips to ``<name>``); unknown names replicate.
    """
    paged = "block_tables" in graph.inputs
    kv_heads = _graph_kv_heads(graph)

    def spec_for(name: str, shape: Tuple[int, ...]) -> P:
        role = serving_value_role(name, shape, paged=paged)
        nd = len(shape)
        if role == "col" and nd >= 1 and _div(shape[-1], mesh, "model"):
            return P(*([None] * (nd - 1) + ["model"]))
        if role == "kv_col":
            # packed (d_model, Hk*dh): shard only on whole kv heads
            if kv_heads and _div(kv_heads, mesh, "model") \
                    and nd >= 1 and _div(shape[-1], mesh, "model"):
                return P(*([None] * (nd - 1) + ["model"]))
            return P()
        if role == "dense_cache" and nd == 4 and _div(shape[2], mesh, "model"):
            return P(None, None, "model", None)
        if role == "paged_pool" and _pool_axis(kv_heads, mesh):
            return P(None, None, "model")
        if role == "kv_scale" and nd == 2 and _div(shape[1], mesh, "model"):
            return P(None, "model")
        return P()

    specs: Dict[str, P] = {}
    for name, ts in graph.inputs.items():
        specs[name] = spec_for(name, tuple(ts.shape))
    for name, arr in graph.params.items():
        specs[name] = spec_for(name, tuple(np.shape(arr)))
    for name in graph.outputs:
        base = name[4:] if name.startswith("new_") else None
        if base is not None and base in specs:
            specs[name] = specs[base]    # cache outputs mirror their input
        else:
            try:
                shape = tuple(graph.spec_of(name).shape)
            except Exception:
                specs[name] = P()        # shape unknown -> replicate
                continue
            specs[name] = spec_for(name, shape)
    return specs


def mesh_axes(mesh: Mesh) -> Dict[str, int]:
    """``{axis_name: size}`` — the serialisable identity of a mesh."""
    return {a: int(mesh.shape[a]) for a in mesh.axis_names}


def check_mesh_compat(recorded: Dict[str, int], mesh: Mesh) -> None:
    """Raise ValueError unless ``mesh`` matches a recorded axis layout.

    Compatible means: same axis names with the same sizes (order-free).
    Specs name mesh axes, so a renamed or resized axis would silently
    re-plan the layout — exactly what a partitioned bundle promises not
    to do."""
    actual = mesh_axes(mesh)
    if actual != dict(recorded):
        raise ValueError(
            f"partitioned Program was saved for mesh axes {dict(recorded)} "
            f"but is being loaded onto {actual}; reload on a mesh with the "
            f"same axis names and sizes, or load with mesh=None and "
            f"re-partition via compile(mesh=...)")
