"""The engine's profiler spans and the Program's node scopes.

A traced engine writes its own host spans into the profiler's trace
(``engine.step`` with its ``tick``, and inside it ``engine.schedule``,
``stepper.stage``, ``stepper.wait``, ``stepper.fetch``, ``engine.emit``),
its bound Programs run under jit names taken from their graphs, and
every XLA op of a Program carries ``<op>/<node name>`` in its
``op_name`` metadata, which leaves the ops themselves unchanged.
"""

import contextlib
import glob
import os
import re
import sys

import jax
import numpy as np
import pytest

from repro.models.graph_lm import GraphLMConfig
from repro.runtime.engine import EngineRequest, build_lm_serving

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.append(os.path.join(ROOT, "bench"))
import program_trace  # noqa: E402

TINY = GraphLMConfig(vocab=61, d_model=32, n_layers=2, n_heads=4,
                     n_kv_heads=2, d_ff=64)
CHILDREN = ["engine.schedule", "stepper.stage", "stepper.wait",
            "stepper.fetch", "engine.emit"]


@pytest.fixture(scope="module")
def engine():
    eng, _ = build_lm_serving(TINY, paged=True, n_slots=2, chunk=4,
                              cache_cap=32, page_size=4)
    return eng


def _submit(eng, uid, n):
    eng.submit(EngineRequest(uid=uid, prompt=np.arange(1, n + 1, dtype=np.int32),
                             max_new_tokens=6))


@pytest.fixture(scope="module")
def traced(engine, tmp_path_factory):
    """Four ticks with work in each, a prefill and three decodes, traced
    after both step shapes were compiled."""
    _submit(engine, 0, 5)
    engine.run()
    _submit(engine, 1, 4)
    _submit(engine, 2, 3)
    log_dir = str(tmp_path_factory.mktemp("trace"))
    ticks = []
    with jax.profiler.trace(log_dir):
        for _ in range(4):
            assert engine.has_work()
            engine.step()
            ticks.append(engine.tick)
    return log_dir, ticks


def test_every_span_nests_in_its_step_in_order(traced):
    log_dir, ticks = traced
    prog = program_trace.load(log_dir)
    steps = [(s, e) for name, s, e in prog["spans"] if name == "engine.step"]
    assert len(steps) == len(ticks)
    others = [x for x in prog["spans"] if x[0] != "engine.step"]
    assert {x[0] for x in others} == set(CHILDREN)
    for s, e in steps:
        inner = [name for name, cs, ce in others if s <= cs and ce <= e]
        assert inner == CHILDREN
    assert len(others) == len(CHILDREN) * len(steps)


def test_the_step_span_carries_its_tick(traced):
    log_dir, ticks = traced
    path = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                         "*.xplane.pb")))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    seen = [dict(ev.stats).get("tick") for plane in data.planes
            for line in plane.lines for ev in line.events
            if ev.name == "engine.step"]
    assert sorted(int(t) for t in seen) == ticks


def test_bound_programs_run_under_their_graph_names(traced, engine):
    log_dir, _ = traced
    path = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                         "*.xplane.pb")))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    names = {ev.name for plane in data.planes for line in plane.lines
             for ev in line.events}
    for prog in (engine.stepper.decode_program, engine.stepper.prefill_program):
        assert any(prog.graph.name in n for n in names), prog.graph.name


def _ops(hlo: str) -> str:
    """HLO text without metadata or the stack-frame tables it indexes."""
    lines = [ln for ln in hlo.splitlines() if not re.match(
        r"^(\d+ |FileNames|FunctionNames|FileLocations|StackFrames)", ln)]
    return re.sub(r", metadata=\{[^}]*\}", "", "\n".join(lines))


def test_node_scopes_are_metadata_only(engine, monkeypatch):
    prog = engine.stepper.decode_program
    scoped = prog.lower().as_text(dialect="hlo", debug_info=True)
    names = re.findall(r'op_name="([^"]*)"', scoped)
    assert any("/paged_decode_attention/l1.attn/" in n for n in names)
    assert any("/embedding/embed_lookup/" in n for n in names)
    assert any("/dense/lm_head/" in n for n in names)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = prog.lower().as_text(dialect="hlo", debug_info=True)
    assert not any("paged_decode_attention" in n
                   for n in re.findall(r'op_name="([^"]*)"', plain))
    assert _ops(scoped) == _ops(plain)
