"""The five readers of the program's own spans, on a hand-built trace
whose answers are worked out by hand."""

import types

import pytest

import program_trace as pt
import run

MS = 1_000_000  # ns
METRICS = ("schedule_idle_ms", "stage_idle_ms", "wait_idle_ms",
           "fetch_idle_ms", "emit_idle_ms")


def tick(t0, bounds, end):
    """An ``engine.step`` from ``t0`` to ``end`` whose five children
    follow each other between the times in ``bounds``."""
    names = ("engine.schedule", "stepper.stage", "stepper.wait",
             "stepper.fetch", "engine.emit")
    return ([("engine.step", t0 * MS, end * MS)]
            + [(n, s * MS, e * MS) for n, s, e in
               zip(names, bounds, bounds[1:])])


def hand_trace():
    # window 0..100 ms.  Step 1 (5..45) makes a decode call whose ops run
    # 10..35; step 2 (50..90) a prefill call (55..75), a copy made while
    # staging (53..54) and a second decode call (76..79); step 3 (95..110)
    # and a step that ends at 2 lie partly outside the window.
    spans = (tick(5, (5, 8, 15, 35, 40, 44), 45)
             + tick(50, (50, 52, 60, 80, 86, 89), 90)
             + [("engine.step", 95 * MS, 110 * MS),
                ("engine.schedule", 95 * MS, 97 * MS),
                ("stepper.stage", 97 * MS, 108 * MS),
                ("engine.step", -10 * MS, 2 * MS),
                ("stepper.fetch", -5 * MS, 1 * MS)])
    ops = [("embed", 10, 20), ("attn", 20, 30), ("head", 30, 34),
           ("copy", 34, 35), ("where", 53, 54), ("prefill", 55, 75),
           ("write", 76, 79), ("write", 98, 104)]
    return {"window": (0, 100 * MS),
            "device_ops": {"/device:TPU:0": [(n, s * MS, e * MS)
                                             for n, s, e in ops]},
            "spans": {"bench.window": [(0, 100 * MS)]},
            "program": {"spans": spans}}


def readers():
    return run.Layout().cell("stablelm-2-12b.decode").readers


def test_each_reader_on_the_hand_built_trace():
    got = {m: readers()[m].read(types.SimpleNamespace(trace=hand_trace()))
           for m in METRICS}
    # idle by innermost span, in ms, over the two steps wholly in the window:
    # schedule 3 + 2 + 2 (95..97, clipped span of step 3);
    # stage (8..15 less 10..15) + (52..60 less 53..54, 55..60)
    #   + (97..100 less 98..100);
    # wait 15..35 all busy, 60..80 less 60..75 and 76..79;
    # fetch 5 + 6 + 0..1 (clipped from -5..1);  emit 4 + 3
    assert got == {"schedule_idle_ms": pytest.approx(3.5),
                   "stage_idle_ms": pytest.approx(2.5),
                   "wait_idle_ms": pytest.approx(1.0),
                   "fetch_idle_ms": pytest.approx(6.0),
                   "emit_idle_ms": pytest.approx(3.5)}


def test_idle_is_given_to_the_innermost_span_only():
    t = types.SimpleNamespace(trace=hand_trace())
    idle = pt.idle_by_span(t)
    # the steps' own time: 44..45, 89..90, and 1..2 of the early step
    assert idle["engine.step"] == pytest.approx(0.003)
    window_idle = 0.100 - 0.051      # busy 10..35, 53..54, 55..75, 76..79, 98..100
    outside = 0.003 + 0.005 + 0.005  # 2..5, 45..50, 90..95
    assert sum(idle.values()) == pytest.approx(window_idle - outside)


def test_exclusive_pieces_of_nested_spans():
    spans = [("a", 0, 10), ("b", 2, 4), ("c", 3, 4), ("b", 6, 8)]
    assert pt.exclusive(spans) == {"a": [(0, 2), (4, 6), (8, 10)],
                                   "b": [(2, 3), (6, 8)], "c": [(3, 4)]}


def test_a_program_without_spans_reads_nothing():
    t = hand_trace()
    t["program"] = {"spans": []}
    bare = types.SimpleNamespace(trace=t)
    assert all(readers()[m].read(bare) is None for m in METRICS)


def test_an_untraced_run_reads_nothing():
    untraced = types.SimpleNamespace(trace=None)
    assert all(readers()[m].read(untraced) is None for m in METRICS)
