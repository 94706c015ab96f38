"""The reduction from a profiler trace to the benchmark's numbers, on a
hand-built trace whose answers are worked out by hand."""

import pytest

import trace_reduce as tr

MS = 1_000_000  # ns


def hand_trace():
    # window 0..100 ms; device ops overlap at 10..30 (two ops) and leave
    # gaps 30..40 (inside a prefill span), 60..80 (inside a step but no
    # call) and 90..100 (outside any step)
    ops = [("matmul", 10 * MS, 25 * MS), ("copy", 20 * MS, 30 * MS),
           ("matmul", 40 * MS, 60 * MS), ("gather", 80 * MS, 90 * MS),
           ("early", -5 * MS, 5 * MS)]
    spans = {"bench.window": [(0, 100 * MS)],
             "bench.step": [(8 * MS, 50 * MS), (55 * MS, 92 * MS)],
             "bench.prefill": [(9 * MS, 45 * MS)],
             "bench.decode": [(78 * MS, 91 * MS)]}
    return {"window": (0, 100 * MS), "device_ops": {"/device:TPU:0": ops},
            "spans": spans}


def test_busy_union_and_idle_share():
    t = hand_trace()
    # 0..5 (clipped) + 10..30 + 40..60 + 80..90 = 5 + 20 + 20 + 10 ms
    assert tr.busy(t, "/device:TPU:0") == [(0, 5 * MS), (10 * MS, 30 * MS),
                                           (40 * MS, 60 * MS),
                                           (80 * MS, 90 * MS)]
    assert tr.busy_s(t) == pytest.approx(0.055)
    assert tr.window_s(t) == pytest.approx(0.1)
    assert 1 - tr.busy_s(t) / tr.window_s(t) == pytest.approx(0.45)


def test_device_time_in_spans():
    t = hand_trace()
    pre = tr.inside(t, "bench.prefill")
    assert pre == [(9 * MS, 45 * MS)]
    # 10..30 and 40..45
    assert tr.device_time_in(t, pre) == pytest.approx(0.025)
    # decode span 78..91 covers the gather 80..90
    assert tr.device_time_in(t, tr.inside(t, "bench.decode")) == \
        pytest.approx(0.010)
    steps = tr.inside(t, "bench.step")
    # 10..30, 40..50, 55..60 and 80..90
    assert tr.device_time_in(t, steps) == pytest.approx(0.045)


def test_spans_partly_outside_the_window_do_not_count():
    t = hand_trace()
    t["spans"]["bench.decode"].append((95 * MS, 120 * MS))
    assert tr.inside(t, "bench.decode") == [(78 * MS, 91 * MS)]


def test_idle_gaps_are_labelled_by_the_host_span():
    t = hand_trace()
    gaps = tr.idle_gaps(t)
    # 5..10 in no step, 30..40 in prefill, 60..80 in a step only,
    # 90..100 outside every step (the step ends at 92: mid 95 is outside)
    assert gaps == [("bench.step", pytest.approx(0.020)),
                    ("bench.prefill", pytest.approx(0.010)),
                    ("no_step", pytest.approx(0.010)),
                    ("no_step", pytest.approx(0.005))]
    assert sum(g for _, g in gaps) == pytest.approx(0.045)


def test_top_ops_sum_by_name_in_the_window():
    t = hand_trace()
    assert tr.top_ops(t) == [("matmul", pytest.approx(0.035)),
                             ("copy", pytest.approx(0.010)),
                             ("gather", pytest.approx(0.010)),
                             ("early", pytest.approx(0.005))]


def test_merge_and_overlap():
    assert tr.merge([(5, 9), (1, 3), (2, 4), (9, 10)]) == [(1, 4), (5, 10)]
    assert tr.overlap([(0, 10), (20, 30)], [(5, 25)]) == 10


def test_a_trace_with_no_device_is_not_busy():
    t = hand_trace()
    t["device_ops"] = {}
    assert tr.busy_s(t) == 0.0
    assert tr.idle_gaps(t) == []
