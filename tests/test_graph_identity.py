"""Pin every serving graph builder's output, node for node.

These are the graphs the benchmark cells run (the paged fp32 decode and
prefill steps of each cell's model family) and the ones the engine's other
serving paths build (paged int8, the dense layout, the speculative draft,
verify and spec-commit Programs).  Each case hashes the ordered
``(name, op, inputs, outputs, sorted attrs)`` of every node plus the
graph's inputs (with their shapes and dtypes) and outputs, at a tiny
config of the family, and compares it with a constant.

A change that alters a graph on purpose updates the constant here and
says so in ``CHANGES.md``; any other difference is a regression in the
graph builders.  ``PYTHONPATH=src python tests/test_graph_identity.py``
prints every case's current fingerprint.
"""

import dataclasses
import hashlib
import re

import pytest

from repro.models.graph_lm import (GraphLMConfig, build_decode_graph,
                                   build_draft_graph,
                                   build_paged_decode_graph,
                                   build_paged_prefill_graph,
                                   build_paged_verify_graph,
                                   build_paged_verify_seq_graph,
                                   build_prefill_graph,
                                   build_spec_commit_graph,
                                   build_verify_graph, init_lm_params)

# the three cell families, cut to a few rows and layers
MHA = GraphLMConfig(vocab=64, d_model=32, n_layers=2, n_heads=4,
                    n_kv_heads=4, d_ff=64)
GQA = GraphLMConfig(vocab=64, d_model=40, n_layers=2, n_heads=8,
                    n_kv_heads=2, d_ff=64, head_dim=10)
MOE = GraphLMConfig(vocab=64, d_model=32, n_layers=4, n_heads=8,
                    n_kv_heads=1, d_ff=64, head_dim=8, windows=(8, 8, 8, 0),
                    n_experts=8, top_k=2, d_expert=16,
                    matmul_precision="highest")

B, CHUNK, N_BLOCKS, PAGE, MAX_PAGES, CAP, SPEC_K = 2, 4, 8, 4, 4, 16, 2
PAGED = dict(n_blocks=N_BLOCKS, page_size=PAGE, max_pages=MAX_PAGES)


def _build(case: str, cfg: GraphLMConfig):
    p = init_lm_params(cfg)
    w = SPEC_K + 1
    builders = {
        "paged_decode": lambda: build_paged_decode_graph(
            cfg, p, batch=B, **PAGED),
        "paged_prefill": lambda: build_paged_prefill_graph(
            cfg, p, batch=B, chunk=CHUNK, **PAGED),
        "paged_kv8_decode": lambda: build_paged_decode_graph(
            cfg, p, batch=B, kv_dtype="int8", **PAGED),
        "paged_kv8_prefill": lambda: build_paged_prefill_graph(
            cfg, p, batch=B, chunk=CHUNK, kv_dtype="int8", **PAGED),
        "dense_decode": lambda: build_decode_graph(
            cfg, p, batch=B, cache_cap=CAP),
        "dense_prefill": lambda: build_prefill_graph(
            cfg, p, batch=B, chunk=CHUNK, cache_cap=CAP),
        "dense_verify": lambda: build_verify_graph(
            cfg, p, batch=B, width=w, cache_cap=CAP),
        "paged_verify": lambda: build_paged_verify_graph(
            cfg, p, batch=B, width=w, **PAGED),
        "draft": lambda: build_draft_graph(
            cfg, p, batch=B, cache_cap=CAP, spec_k=SPEC_K),
        "verify_seq": lambda: build_paged_verify_seq_graph(
            cfg, p, batch=B, width=w, **PAGED),
        "spec_commit": lambda: build_spec_commit_graph(
            cfg, batch=B, width=w, **PAGED),
    }
    return builders[case]()


def _fingerprint(graph) -> str:
    # the verify graphs attend through the chunk ops; an attention op once
    # named for the verify step folds onto its chunk op, so the constants
    # below hold for either spelling
    nodes = [(n.name, re.sub(r"verify(?=_attention)", "chunk", n.op),
              tuple(n.inputs), tuple(n.outputs), tuple(sorted(
                  (k, repr(v)) for k, v in n.attrs.items())))
             for n in graph.nodes]
    inputs = [(name, tuple(int(d) for d in spec.shape), str(spec.dtype))
              for name, spec in graph.inputs.items()]
    text = repr((graph.name, nodes, inputs, tuple(graph.outputs)))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


CASES = {
    ("paged_decode", "mha"): "60323103a3b3100b",
    ("paged_decode", "gqa"): "9aba1cb0a38203e2",
    ("paged_decode", "moe"): "51c7ac761fe3793b",
    ("paged_prefill", "mha"): "93f90294fa07777d",
    ("paged_prefill", "gqa"): "4e692e51d39e1c95",
    ("paged_prefill", "moe"): "a037f54ecafc5e3a",
    ("paged_kv8_decode", "mha"): "d655b5b75e6ec6e0",
    ("paged_kv8_decode", "gqa"): "5a4e885e981c4404",
    ("paged_kv8_prefill", "mha"): "da112e07e8ab5cd6",
    ("paged_kv8_prefill", "gqa"): "aedced9c4de99ef3",
    ("dense_decode", "gqa"): "2765f2cee381669b",
    ("dense_prefill", "gqa"): "bbeefa1327775f90",
    ("dense_verify", "gqa"): "6a61ad74f94ec4fe",
    ("paged_verify", "gqa"): "f3d6a4bc2afa9170",
    ("draft", "mha"): "db5fbd12b2839b66",
    ("verify_seq", "gqa"): "ccdba6ec734b20fa",
    ("spec_commit", "gqa"): "d19d0d00da389a0a",
}
CONFIGS = {"mha": MHA, "gqa": GQA, "moe": MOE}


@pytest.mark.parametrize("case,family", sorted(CASES),
                         ids=[f"{c}-{f}" for c, f in sorted(CASES)])
def test_graph_fingerprint(case, family):
    graph = _build(case, CONFIGS[family])
    assert _fingerprint(graph) == CASES[case, family], \
        f"{graph.name} ({family}) changed node for node"


if __name__ == "__main__":
    for key in sorted(CASES):
        print(f"    {key!r}: "
              f"{_fingerprint(_build(key[0], CONFIGS[key[1]]))!r},")


@pytest.mark.parametrize("precision", [None, "highest"])
@pytest.mark.parametrize("case", ["draft", "verify_seq"])
def test_unrolled_stages_are_the_decode_step(case, precision):
    """Each stage of the draft (dense) and kv8 verify-seq Programs holds
    the decode step's nodes, ops and attrs (``matmul_precision`` included)
    under the stage's ``.s{j}`` suffix, which is what makes a stage's
    logits the decode Program's."""
    cfg = dataclasses.replace(GQA, matmul_precision=precision)
    p = init_lm_params(cfg)
    if case == "draft":
        unrolled = _build("draft", cfg)
        decode = build_decode_graph(cfg, p, batch=B, cache_cap=CAP)
    else:
        unrolled = _build("verify_seq", cfg)
        decode = build_paged_decode_graph(cfg, p, batch=B, kv_dtype="int8",
                                          **PAGED)
    nodes = {n.name: n for n in unrolled.nodes}
    for j in range(SPEC_K):
        for n in decode.nodes:
            staged = nodes[f"{n.name}.s{j}"]
            assert (staged.op, staged.attrs) == (n.op, n.attrs), staged.name
