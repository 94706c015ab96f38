"""Backend-selector fallback tests.

The policies must degrade cleanly when a fancy backend's ``supports()``
predicate rejects the node's shapes (e.g. pallas block-divisibility), and
must not crash on ops that only have a single registered backend (e.g.
``swiglu``): the chosen backend is always one of the
registered-and-supported set.  The autotuner must also skip measurement
entirely when there is only one candidate — there is nothing to compare.
"""

import numpy as np
import pytest

import repro  # noqa: F401  (registers every op/backend)
from repro.core import (AutotunePolicy, CostModelPolicy, FixedPolicy,
                        Node, TensorSpec, backends_for)


def _attn_node_and_specs():
    # seq 7 with an explicit block_q=4 -> 7 % 4 != 0 -> pallas unsupported
    node = Node("attn", "attention", ["q", "k", "v"], ["o"],
                attrs={"block_q": 4, "block_kv": 4, "causal": True})
    q = TensorSpec((1, 7, 2, 8), "float32")
    kv = TensorSpec((1, 7, 1, 8), "float32")
    return node, [q, kv, kv]


def _grouped_conv_node_and_specs():
    # groups=2 -> the pallas GEMM conv rejects; ref/xla remain
    node = Node("c", "conv2d", ["x", "w"], ["y"], attrs={"groups": 2})
    return node, [TensorSpec((1, 4, 4, 4), "float32"),
                  TensorSpec((3, 3, 2, 4), "float32")]


def _single_backend_node_and_specs():
    # swiglu has exactly one backend (ref) — XLA fuses it well on its own
    node = Node("sw", "swiglu", ["g", "u"], ["o"])
    return node, [TensorSpec((2, 8), "float32"), TensorSpec((2, 8), "float32")]


@pytest.mark.parametrize("make", [_attn_node_and_specs,
                                  _grouped_conv_node_and_specs,
                                  _single_backend_node_and_specs])
def test_costmodel_policy_chooses_supported(make):
    node, specs = make()
    avail = backends_for(node.op, specs, node.attrs)
    assert avail, "test premise: at least one supported backend"
    choice = CostModelPolicy().resolve(node, specs)
    assert choice in avail


def test_pallas_actually_rejected_by_supports():
    node, specs = _attn_node_and_specs()
    all_backends = backends_for(node.op)
    supported = backends_for(node.op, specs, node.attrs)
    assert "pallas" in all_backends
    assert "pallas" not in supported      # the shape filter really fired
    node2, specs2 = _grouped_conv_node_and_specs()
    assert "pallas" not in backends_for(node2.op, specs2, node2.attrs)


def test_single_backend_op_resolves_to_ref():
    node, specs = _single_backend_node_and_specs()
    assert backends_for(node.op, specs, node.attrs) == ["ref"]
    assert CostModelPolicy().resolve(node, specs) == "ref"
    assert FixedPolicy(prefer=("pallas", "xla")).resolve(node, specs) == "ref"


def test_autotune_policy_degrades_cleanly():
    pol = AutotunePolicy(reps=1)
    for make in (_grouped_conv_node_and_specs, _single_backend_node_and_specs):
        node, specs = make()
        avail = backends_for(node.op, specs, node.attrs)
        choice = pol.resolve(node, specs)
        assert choice in avail
    # grouped conv (ref/xla) was measured; single-backend swiglu was not
    assert pol.n_measured == 1


def test_autotune_skips_single_candidate_measurement():
    """Regression: one registered (or candidate-filtered) backend used to
    burn warm-up + reps iterations to "choose" among one option."""
    node, specs = _single_backend_node_and_specs()
    pol = AutotunePolicy(reps=1)
    assert pol.resolve(node, specs) == "ref"
    assert pol.n_measured == 0 and not pol._timings
    # same skip when `candidates` narrows a multi-backend op down to one
    conv, conv_specs = _grouped_conv_node_and_specs()
    pol2 = AutotunePolicy(reps=1, candidates=("xla",))
    assert pol2.resolve(conv, conv_specs) == "xla"
    assert pol2.n_measured == 0 and not pol2._timings


def test_autotune_multibackend_chunk_attention():
    node = Node("a", "chunk_attention", ["q", "k", "v", "s"], ["o"])
    specs = [TensorSpec((1, 2, 2, 4), "float32"),
             TensorSpec((1, 8, 1, 4), "float32"),
             TensorSpec((1, 8, 1, 4), "float32"),
             TensorSpec((1,), "int32")]
    avail = backends_for(node.op, specs, node.attrs)
    assert set(avail) >= {"ref", "xla"}
    pol = AutotunePolicy(reps=1, candidates=("ref", "xla"))
    assert pol.resolve(node, specs) in avail
    assert pol.n_measured == 1


def test_pinned_unsupported_backend_raises():
    node, specs = _attn_node_and_specs()
    node.backend = "pallas"
    with pytest.raises(ValueError, match="pinned backend"):
        FixedPolicy().resolve(node, specs)


def test_autotune_measure_raises_when_admitted_backend_fails(monkeypatch):
    """supports() is the only filter: a backend it admits that then fails
    to run is an error, not a silent ``inf`` that drops it from the
    choice (on a chip that would hide a kernel Mosaic refuses)."""
    from repro.core.registry import OpImpl, get_op

    def broken(inputs, attrs):
        raise RuntimeError("kernel refused by the compiler")

    node = Node("a", "chunk_attention", ["q", "k", "v", "s"], ["o"])
    specs = [TensorSpec((1, 2, 2, 4), "float32"),
             TensorSpec((1, 8, 1, 4), "float32"),
             TensorSpec((1, 8, 1, 4), "float32"),
             TensorSpec((1,), "int32")]
    monkeypatch.setitem(get_op("chunk_attention").impls, "broken",
                        OpImpl("chunk_attention", "broken", broken,
                               supports=lambda specs, attrs: True))
    assert "broken" in backends_for(node.op, specs, node.attrs)
    pol = AutotunePolicy(reps=1, candidates=("xla", "broken"))
    with pytest.raises(RuntimeError, match="refused"):
        pol.measure(node.op, specs, node.attrs)
    with pytest.raises(RuntimeError, match="refused"):
        pol.resolve(node, specs)
