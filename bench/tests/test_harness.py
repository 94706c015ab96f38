"""The harness finds everything by name, keeps to the naming rules, and
refuses to run without a chip."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import run

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixture")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def fixture_layout():
    return run.Layout(os.path.join(FIXTURE, "BENCHMARK.json"),
                      roots=[FIXTURE, run.BENCH])


def test_a_cell_is_found_from_files_alone():
    """The fixture adds a configuration, two mixes, their check limits and
    a metric reader as files under tests/fixture; run.py is not edited."""
    cell = fixture_layout().cell("tiny.chat")
    assert cell.cfg["name"] == "tiny"
    assert cell.mix["arrival"]["kind"] == "poisson"
    assert cell.checks["served_logit_gap4"] > 0
    assert "served_tokens" in cell.readers          # the fixture's reader
    assert "prefill_mfu" in cell.readers            # a reader of bench/
    assert "batch_occupancy" not in cell.readers    # another cell's
    assert [m["name"] for m in cell.end_to_end] == [
        "ttft_p90_s", "itl_p95_s", "setup_s"]
    # longest prompt 60 + longest answer 24, in whole pages of 8
    assert cell.cache_cap == 88


def test_an_unknown_name_is_an_error():
    with pytest.raises(run.BenchError):
        fixture_layout().cell("tiny.nothing")


def test_benchmark_json_names_and_units():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    names = ([c["name"] for c in spec["configs"]]
             + [w["name"] for w in spec["workloads"]]
             + [w["config"] for w in spec["workloads"]]
             + [w["traffic"] for w in spec["workloads"]]
             + [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
             + [k for c in spec["configs"] for k in c["reduced"]])
    assert all(NAME.match(n) for n in names), names
    units = [m["unit"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert all(UNIT.match(u) for u in units), units
    assert len(set(w["name"] for w in spec["workloads"])) == \
        len(spec["workloads"])


def test_every_cell_of_benchmark_json_resolves():
    layout = run.Layout()
    for w in layout.spec["workloads"]:
        cell = layout.cell(w["name"])
        assert cell.checks["served_logit_gap4"] > 0
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in e2e, (w["name"], m["name"])


def test_a_finished_window_frees_the_engine():
    """What a window returns holds no reference to the engine, so that the
    reference runs after the engine's memory is freed."""
    import gc
    import weakref
    session = run.build(fixture_layout().cell("tiny.decode"), 5)
    win = run.serve(session, seconds=0.5, traced=False)
    engine = weakref.ref(session.engine)
    session.engine = None
    gc.collect()
    assert engine() is None
    assert any(s.logits for s in win.served)


def run_cli(cwd, script):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, script, "--workload", "phi3-mini-3.8b.decode",
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_chip_exits_nonzero_and_prints_no_result():
    p = run_cli(run.ROOT, os.path.join("bench", "run.py"))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


def test_a_checkout_of_only_the_benchmark_fails(tmp_path):
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(run.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".trace", "__pycache__"))
    p = run_cli(str(tmp_path), os.path.join("bench", "run.py"))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
