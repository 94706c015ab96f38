"""Immutable compiled Program artifact + the top-level ``compile`` entrypoint.

This splits the old monolithic ``Executor`` into its two real halves:

* :func:`compile` — the staged front half: run a pass pipeline
  (:class:`~repro.core.pipeline.PassManager`), resolve a backend per node
  under a :class:`~repro.core.selector.BackendPolicy`, freeze the result.
* :class:`Program` — the back half: an immutable artifact holding the
  simplified graph, the frozen backend assignment, the analytic cost table,
  and the jitted callable.  Programs can be saved to / loaded from an OXF
  bundle (the assignment is pinned into each node's ``backend`` field), so a
  tuned deployment survives process restarts without re-tuning.

Typical use::

    from repro.core import compile, AutotunePolicy

    prog = compile(graph, policy=AutotunePolicy(cache_path="tune.json"))
    (y,) = prog(x=x)
    prog.save("model_dir")           # graph + weights + frozen assignment
    prog2 = Program.load("model_dir")  # no re-measurement, same assignment
"""

from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass
from types import MappingProxyType
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.importer import load_graph, save_graph
from repro.core.ir import Graph, Node, TensorSpec, topological_order
from repro.core.pipeline import PassManager, PassStats, default_pipeline
from repro.core.registry import Cost, get_impl
from repro.core.selector import BackendPolicy, FixedPolicy

__all__ = ["Program", "NodeReport", "compile"]


def _partition_spec_to_json(spec) -> List[Any]:
    """PartitionSpec -> JSON dim entries (None | axis name | [axis names])."""
    return [list(e) if isinstance(e, (tuple, list)) else e for e in spec]


def _partition_spec_from_json(entries: Sequence[Any]):
    from jax.sharding import PartitionSpec
    return PartitionSpec(
        *[tuple(e) if isinstance(e, list) else e for e in entries])


@dataclass
class NodeReport:
    name: str
    op: str
    backend: str
    seconds: float
    cost: Cost
    out_spec: TensorSpec


class Program:
    """A compiled inference program: graph + frozen backend assignment.

    Instances are immutable by convention (the assignment mapping is
    read-only; the graph must not be mutated after construction) — compile a
    new Program instead of editing one.  The jitted callable is built lazily
    on first call and cached.
    """

    def __init__(self, graph: Graph, assignment: Mapping[str, str],
                 pass_stats: Sequence[PassStats] = (), mesh: Any = None):
        from repro.core.passes import infer_shapes
        self._mesh = mesh
        # freeze the partition layout stamped by the `partition` pass before
        # any Graph rebuild below can drop the dynamic attributes
        part_specs = getattr(graph, "partition_specs", None)
        self._partition: Optional[Dict[str, Mapping[str, Any]]] = None
        if part_specs is not None:
            self._partition = {
                "mesh": MappingProxyType(
                    dict(getattr(graph, "partition_mesh", {}) or {})),
                "specs": MappingProxyType(dict(part_specs)),
            }
        self._graph = graph if graph.value_info else infer_shapes(graph)
        self._order = topological_order(self._graph)
        missing = [n.name for n in self._order if n.name not in assignment]
        if missing:
            raise ValueError(f"assignment missing nodes: {missing[:5]}")
        self._assignment: Mapping[str, str] = MappingProxyType(dict(assignment))
        self._pass_stats: Tuple[PassStats, ...] = tuple(pass_stats)
        # Frozen analytic cost table: node name -> (backend, Cost).
        table: Dict[str, Tuple[str, Cost]] = {}
        for node in self._order:
            b = self._assignment[node.name]
            in_specs = [self._graph.spec_of(v) for v in node.inputs]
            table[node.name] = (b, get_impl(node.op, b).cost(in_specs, node.attrs))
        self._cost_table: Mapping[str, Tuple[str, Cost]] = MappingProxyType(table)
        self._jitted: Optional[Callable] = None
        self._stored: Optional[Dict[str, Any]] = None

    # ------------------------------------------------------------------ #
    @property
    def graph(self) -> Graph:
        return self._graph

    @property
    def assignment(self) -> Dict[str, str]:
        """node name -> chosen backend (copy; the Program's own is frozen)."""
        return dict(self._assignment)

    @property
    def pass_stats(self) -> Tuple[PassStats, ...]:
        """Per-pass compile-time profile from the pipeline that built this."""
        return self._pass_stats

    @property
    def cost_table(self) -> Mapping[str, Tuple[str, Cost]]:
        return self._cost_table

    @property
    def partition(self) -> Optional[Dict[str, Mapping[str, Any]]]:
        """Frozen partition layout, or None for unpartitioned Programs.

        ``{"mesh": {axis: size}, "specs": {value name: PartitionSpec}}``
        with a spec for every graph input, param and output — stamped by
        ``compile(mesh=...)``'s `partition` pass, serialized through OXF,
        and used by the serving engine to ``jax.device_put`` caches and
        params onto NamedShardings with zero re-planning after a load."""
        return self._partition

    def costs(self) -> List[Tuple[Node, str, Cost]]:
        return [(node, *self._cost_table[node.name]) for node in self._order]

    def total_cost(self) -> Cost:
        total = Cost()
        for _, cost in self._cost_table.values():
            total = total + cost
        return total

    # ------------------------------------------------------------------ #
    def _trace(self, params: Dict[str, Any], inputs: Dict[str, Any]) -> Tuple[Any, ...]:
        env: Dict[str, Any] = {}
        env.update(params)
        env.update(inputs)
        for node in self._order:
            fn = get_impl(node.op, self._assignment[node.name])
            args = [env[v] for v in node.inputs]
            # every XLA op of the node carries ``<op>/<node name>`` in its
            # op_name metadata, so a device trace names the node it ran for
            with jax.named_scope(node.op), jax.named_scope(node.name):
                outs = fn(args, node.attrs)
            for v, val in zip(node.outputs, outs):
                env[v] = val
        return tuple(env[v] for v in self._graph.outputs)

    def callable(self) -> Callable[..., Tuple[Any, ...]]:
        """Returns jitted ``f(inputs: dict, params: dict|None) -> tuple``.

        ``params`` defaults to the graph's stored parameters; passing them
        explicitly supports functional weight updates (training loops)."""
        if self._jitted is None:
            jf = jax.jit(self._trace)
            stored = self._stored_params()

            def call(inputs: Dict[str, Any], params: Optional[Dict[str, Any]] = None):
                return jf(stored if params is None else params, inputs)

            self._jitted = call
        return self._jitted

    def _stored_params(self) -> Dict[str, Any]:
        """Device copies of the graph params, built once and shared by
        every entry point (``__call__`` and each ``bind()``) so N bound
        callables don't hold N copies of the weights.  A Program compiled
        for a mesh places each param on its partition's NamedSharding, so
        no call has to move weights between devices."""
        if self._stored is None:
            params = self._graph.params.items()
            if self._mesh is not None and self._partition is not None:
                specs = self._partition["specs"]
                self._stored = {k: jax.device_put(
                    v, jax.sharding.NamedSharding(self._mesh, specs[k]))
                    for k, v in params}
            else:
                self._stored = {k: jnp.asarray(v) for k, v in params}
        return self._stored

    def __call__(self, **inputs: Any) -> Tuple[Any, ...]:
        missing = set(self._graph.inputs) - set(inputs)
        if missing:
            raise ValueError(f"missing graph inputs: {sorted(missing)}")
        return self.callable()(inputs)

    def bind(self, *names: str,
             donate: Sequence[str] = ()) -> Callable[..., Tuple[Any, ...]]:
        """Positional fast-call path: ``bind("x", "y")`` returns
        ``f(x_arr, y_arr) -> outputs`` with stored params closed over and
        input names validated once, here, instead of per call.  This is
        the serving engine's per-step dispatch: on a hot loop the kwargs
        packing and missing-input check of :meth:`__call__` are measurable
        overhead (``benchmarks/serve_bench.py`` reports both paths).

        ``donate`` names inputs whose buffers the caller will not reuse —
        functional state threaded through the call, like the serving
        engine's KV caches — letting XLA alias each into the output
        ``new_<name>`` that updates it instead of copying (a no-op on
        backends without donation support, e.g. CPU).  A donated buffer is
        consumed: pass the previous call's output, never the same array
        twice.

        XLA aliases each donated argument to the first output of its shape
        not yet taken, in argument order; in any other order an output is
        handed another input's buffer, and the step then copies whole
        buffers (a KV page pool per layer) to keep both alive.  So the
        jitted step takes the donated inputs last, in the order of the
        ``new_<name>`` outputs they become, whatever order ``names`` gives.

        With no arguments, inputs bind in the graph's declared order.
        Each ``bind()`` builds its own jitted entry point — bind once and
        reuse the returned callable.  Its ``lower(params, *args)`` lowers
        that entry point for the given params and arguments (shapes will
        do, in the bound order), so the step can be compiled as it runs
        for a described device."""
        order: Tuple[str, ...] = names or tuple(self._graph.inputs)
        unknown = set(order) - set(self._graph.inputs)
        if unknown:
            raise ValueError(f"not graph inputs: {sorted(unknown)}")
        if set(order) != set(self._graph.inputs):
            missing = set(self._graph.inputs) - set(order)
            raise ValueError(f"bind() must cover every input; missing {sorted(missing)}")
        bad_donate = set(donate) - set(order)
        if bad_donate:
            raise ValueError(f"donate names not inputs: {sorted(bad_donate)}")
        stored = self._stored_params()
        rank = {v: i for i, v in enumerate(self._graph.outputs)}
        donated = sorted((n for n in order if n in set(donate)),
                         key=lambda n: rank.get(f"new_{n}", len(rank)))
        call_order = tuple(n for n in order if n not in set(donate)) \
            + tuple(donated)
        perm = tuple(order.index(n) for n in call_order)
        kept = len(call_order) - len(donated)

        def positional(params: Dict[str, Any], *args: Any) -> Tuple[Any, ...]:
            return self._trace(params, dict(zip(call_order, args)))

        # the compiled module, and so each device op in a trace, is named
        # after the graph (``jit_graph_lm_paged_decode_b16_t1``)
        positional.__name__ = positional.__qualname__ = self._graph.name
        jf = jax.jit(positional,
                     donate_argnums=tuple(range(1 + kept, 1 + len(call_order))))

        def fast(*args: Any) -> Tuple[Any, ...]:
            return jf(stored, *[args[i] for i in perm])

        fast.lower = lambda params, *args: jf.lower(
            params, *[args[i] for i in perm])
        return fast

    # ------------------------------------------------------------------ #
    def lower(self, **input_specs: jax.ShapeDtypeStruct):
        """``jax.jit(...).lower(...)`` for dry-run / cost analysis."""
        # shapes only: no param is copied to a device to be lowered
        stored = {k: jax.ShapeDtypeStruct(
                      np.shape(v),
                      jax.dtypes.canonicalize_dtype(np.result_type(v)))
                  for k, v in self._graph.params.items()}
        specs = input_specs or {
            k: jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype))
            for k, s in self._graph.inputs.items()}
        return jax.jit(self._trace).lower(stored, specs)

    # ------------------------------------------------------------------ #
    def run_instrumented(self, **inputs: Any) -> Tuple[Tuple[Any, ...], List[NodeReport]]:
        """Eager per-node execution with wall-clock timing — the paper's
        individual-layer evaluation. Each node's impl is jitted separately
        (so we time the op, not Python overhead), warmed once, then timed."""
        env: Dict[str, Any] = {k: jnp.asarray(v) for k, v in self._graph.params.items()}
        env.update({k: jnp.asarray(v) for k, v in inputs.items()})
        reports: List[NodeReport] = []
        for node in self._order:
            backend = self._assignment[node.name]
            fn = get_impl(node.op, backend)
            args = [env[v] for v in node.inputs]
            jf = jax.jit(lambda a, _fn=fn, _at=node.attrs: _fn(a, _at))
            outs = jf(args)
            jax.block_until_ready(outs)  # warm
            t0 = time.perf_counter()
            outs = jf(args)
            jax.block_until_ready(outs)
            dt = time.perf_counter() - t0
            reports.append(NodeReport(
                name=node.name, op=node.op, backend=backend, seconds=dt,
                cost=self._cost_table[node.name][1],
                out_spec=self._graph.spec_of(node.outputs[0])))
            for v, val in zip(node.outputs, outs):
                env[v] = val
        return tuple(env[v] for v in self._graph.outputs), reports

    # ------------------------------------------------------------------ #
    # Persistence (OXF bundle: model.json + weights.npz + program.json)
    # ------------------------------------------------------------------ #
    def save(self, path: str) -> None:
        """Serialize graph, weights AND the frozen backend assignment.

        The assignment rides inside the OXF model.json (each node's
        ``backend`` field is pinned), so any OXF loader reconstructs the
        same per-node backends; ``program.json`` additionally records the
        assignment and cost table for human inspection."""
        pinned = self._graph.clone()
        for node in pinned.nodes:
            node.backend = self._assignment[node.name]
        save_graph(pinned, path)
        from repro.core.quant import is_quantized
        meta = {
            "assignment": dict(self._assignment),
            "cost_table": {name: {"backend": b, "flops": c.flops, "bytes": c.bytes}
                           for name, (b, c) in self._cost_table.items()},
            "quantized": is_quantized(self._graph),
        }
        if self._partition is not None:
            # written only for partitioned Programs — unpartitioned bundles
            # keep their exact pre-existing bytes (OXF additive evolution)
            meta["partition"] = {
                "mesh": dict(self._partition["mesh"]),
                "specs": {name: _partition_spec_to_json(spec)
                          for name, spec in self._partition["specs"].items()},
            }
        with open(os.path.join(path, "program.json"), "w") as f:
            json.dump(meta, f, indent=1, sort_keys=True)

    @classmethod
    def load(cls, path: str, policy: Optional[BackendPolicy] = None,
             mesh: Optional[Any] = None) -> "Program":
        """Rebuild a Program from :meth:`save` output.  The pinned per-node
        backends win over ``policy`` (which only fills gaps, e.g. for
        bundles written by a plain ``save_graph``), so no re-tuning or
        re-measurement happens here.

        A bundle saved from a partitioned Program restores its recorded
        PartitionSpecs verbatim — zero re-planning.  Passing ``mesh``
        validates the recorded axis layout against it (clear ValueError on
        mismatch); for bundles without a recorded partition, ``mesh``
        partitions the loaded graph fresh via the `partition` pass."""
        g = load_graph(path)
        part = None
        meta_path = os.path.join(path, "program.json")
        if os.path.exists(meta_path):
            with open(meta_path) as f:
                part = json.load(f).get("partition")
        if part is None:
            return compile(g, policy=policy, pipeline=(), mesh=mesh)
        if mesh is not None:
            from repro.sharding.specs import check_mesh_compat
            check_mesh_compat(part["mesh"], mesh)
        prog = compile(g, policy=policy, pipeline=())
        prog._mesh = mesh
        prog._partition = {
            "mesh": MappingProxyType(
                {a: int(s) for a, s in part["mesh"].items()}),
            "specs": MappingProxyType(
                {n: _partition_spec_from_json(e)
                 for n, e in part["specs"].items()}),
        }
        return prog


def compile(graph: Graph, policy: Optional[BackendPolicy] = None,
            pipeline: Optional[Union[PassManager, Sequence]] = None,
            *, validate: bool = False, quantize: Optional[str] = None,
            calib_data: Any = None,
            calib_ranges: Optional[Mapping[str, Any]] = None,
            mesh: Optional[Any] = None) -> Program:
    """Graph -> Program: the staged compilation entrypoint.

    Parameters
    ----------
    graph:
        The input GraphIR (left untouched).
    policy:
        Backend selection policy; defaults to :class:`FixedPolicy`
        (xla-then-ref).  Per-node ``Node.backend`` pins always win.
    pipeline:
        ``None`` (default) runs the standard simplify pipeline; a
        :class:`PassManager` runs as given; a sequence of pass
        names/callables is wrapped in a PassManager; an empty sequence
        skips rewriting entirely (shape inference still happens).
    validate:
        Forwarded to the default pipeline's inter-pass validation.
    quantize:
        ``"int8"`` runs post-training quantization as an extra compile
        stage after the simplify pipeline: calibration (when
        ``calib_data`` is given) followed by the
        :func:`repro.core.quant.quantize_graph` rewrite.  Weights become
        per-channel int8 params; activation scales are frozen from the
        calibration ranges.
    calib_data:
        Representative inputs for the calibration observer — a dict of
        input arrays, a sequence of dicts, or (single-input graphs) a bare
        array.  Without it, quantization is weight-only and the ``ref``
        int8 backend falls back to dynamic per-batch activation scales.
    calib_ranges:
        Precomputed value ranges (``repro.core.quant.calibrate`` output),
        used instead of running calibration here.  This is how several
        shape variants of one model (the serving engine's batched decode /
        prefill Programs and the unbatched reference — same value names,
        different batch/chunk) share one set of activation scales and stay
        numerically identical per sequence.  Mutually exclusive with
        ``calib_data``.
    mesh:
        A ``jax.sharding.Mesh``.  When given, the `partition` pass runs as
        the final compile stage (after every rewrite, so rebuilt Graph
        objects cannot drop the layout): every input/param/output is
        stamped with a PartitionSpec from the serving rules in
        :mod:`repro.sharding.specs`, frozen into ``Program.partition`` and
        serialized through OXF by :meth:`Program.save`.
    """
    from repro.core.passes import infer_shapes
    if pipeline is None:
        pipeline = default_pipeline(validate=validate)
    elif not isinstance(pipeline, PassManager):
        pipeline = PassManager(list(pipeline), validate=validate, name="custom")
    g = pipeline.run(graph)
    if quantize is not None:
        from repro.core import quant
        if quantize != "int8":
            raise ValueError(f"unsupported quantize mode {quantize!r} (only 'int8')")
        if calib_data is not None and calib_ranges is not None:
            raise ValueError("pass calib_data or calib_ranges, not both")
        if calib_ranges is not None:
            ranges: Any = calib_ranges
        else:
            ranges = (quant.calibrate(g, calib_data)
                      if calib_data is not None else None)
        g = quant.quantize_graph(g, ranges)
    if not g.value_info:
        g = infer_shapes(g)
    pass_stats = tuple(pipeline.stats)
    if mesh is not None:
        from repro.core.pipeline import make_partition_pass
        pmesh = PassManager([make_partition_pass(mesh)], name="partition")
        g = pmesh.run(g)
        pass_stats += tuple(pmesh.stats)
    policy = policy or FixedPolicy()
    assignment: Dict[str, str] = {}
    for node in topological_order(g):
        in_specs = [g.spec_of(v) for v in node.inputs]
        assignment[node.name] = policy.resolve(node, in_specs)
    return Program(g, assignment, pass_stats=pass_stats, mesh=mesh)
