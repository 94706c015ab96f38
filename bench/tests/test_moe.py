"""The mixture-of-experts configuration: its sizes, operations and bytes
checked by hand at the published widths, its three readers on a
hand-built trace, and a tiny cell of its family served, checked and
controlled on the CPU."""

import os

import numpy as np
import pytest

import moe_trace
import run
from models import moe_lm
from test_harness import FIXTURE

MS = 1_000_000  # ns
CELL = "mellum2-12b-a2.5b.decode"
MOE_FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "moe_fixture")


def mellum():
    return run.Layout().cell(CELL)


# --------------------------------------------------------------------------- #
# sizes, operations and bytes at the published widths (4 layers)
# --------------------------------------------------------------------------- #

ATTN = 2304 * 4096 + 2 * 2304 * 512 + 4096 * 2304      # 21,233,664
ROUTER = 2304 * 64                                      # 147,456
EXPERT = 3 * 2304 * 896                                 # 6,193,152
HEAD = 2304 * 98304                                     # 226,492,416


def test_shapes_by_hand():
    shapes = moe_lm._shapes(mellum().cfg)
    n = {k: int(np.prod(s)) for k, s in shapes.items()}
    layer = sum(v for k, v in n.items() if k.startswith("l0."))
    assert (ATTN, ROUTER, 64 * EXPERT) == (21_233_664, 147_456, 396_361_728)
    assert layer == ATTN + ROUTER + 64 * EXPERT + 2 * 2304 == 417_747_456
    assert sum(n.values()) == 4 * layer + 2 * HEAD + 2304 == 2_123_976_960
    assert shapes["l3.we_down"] == (64, 896, 2304)
    assert moe_lm.kv_bytes_per_token(mellum().cfg) == 2 * 4 * 4 * 128 * 4


def test_serving_config_and_windows():
    from repro.models.graph_lm import GraphLMConfig
    g = GraphLMConfig(**moe_lm.serving_config(mellum().cfg))
    assert g.d_head == 128 != g.d_model // g.n_heads
    assert g.windows == (1024, 1024, 1024, 0)
    assert (g.n_experts, g.top_k, g.d_expert) == (64, 8, 896)
    assert g.matmul_precision == "highest"


def test_matmul_params_and_row_flops_by_hand():
    cfg = mellum().cfg
    active = 4 * (ATTN + ROUTER + 8 * EXPERT) + HEAD
    assert moe_lm.matmul_params(cfg) == active == 510_197_760
    # a row at position 1099 sees 1024 keys on each sliding layer and
    # 1100 on the full one
    keys = 3 * 1024 + 1100
    assert moe_lm.row_flops(cfg, [1100]) == 2 * active + 4 * 32 * 128 * keys
    assert moe_lm.row_flops(cfg, [5, 6]) == \
        2 * (2 * active) + 4 * 32 * 128 * 4 * 11


def test_decode_step_bytes_by_hand():
    cfg = mellum().cfg
    hit = 64 * (1 - (7 / 8) ** 16)
    assert moe_lm.experts_hit(cfg, 16) == pytest.approx(hit)
    assert hit == pytest.approx(56.44, abs=0.01)
    dense = 4 * (ATTN + ROUTER) + HEAD + 9 * 2304
    row = 2 * 4 * 128 * 4                          # K and V of one layer

    def slot(n):
        rows = 3 * min(n + 1, 1024) + (n + 1)
        return row * rows + 2304 * 4 + 98304 * 4

    lengths = [100] * 8 + [1200] * 8
    want = (dense + 4 * hit * EXPERT) * 4 + 8 * slot(100) + 8 * slot(1200)
    assert moe_lm.decode_step_bytes(cfg, lengths) == pytest.approx(want)
    # the experts' share: 5.59 GB of the step at 16 rows
    assert 4 * hit * EXPERT * 4 == pytest.approx(5.59e9, rel=1e-3)


# --------------------------------------------------------------------------- #
# the three readers on a hand-built trace
# --------------------------------------------------------------------------- #

def hand_record(markers):
    """Window 0..100 ms, two decode calls (spans 10..30 and 50..70).  The
    first runs expert ops 12..20 (a grouped matmul, then a batched one)
    and the head 20..26; the second expert ops 52..56 and 56..60 and the
    head 60..68; a third expert op 80..90 lies outside every decode
    span.  Op events are named by their HLO text, operands included."""
    head = ("%fusion.116 = f32[16,98304] fusion(f32[2304,98304] "
            "%params__head_w__.1, f32[16,2304] %get-tuple-element.9)")
    ops = [("%ragged-dot-none = f32[128,896] custom-call(s32[1] %gte.8, "
            "f32[128,2304] %fusion.3)", 12, 16),
           ("%fusion.24 = f32[16,2304] fusion(f32[64,16,896] %fusion.46, "
            "f32[64,896,2304] %params__l1_we_down__.1)", 16, 20),
           (head, 20, 26),
           ("%fusion.41 = f32[64,16,896] fusion(f32[64,2304,896] "
            "%params__l0_we_gate__.1, f32[16,2304] %fusion.6)", 52, 56),
           ("%fusion.43 = f32[64,16,896] fusion(f32[64,2304,896] "
            "%params__l0_we_up__.1, f32[16,2304] %fusion.6)", 56, 60),
           (head, 60, 68),
           ("%ragged-dot-none.3 = f32[16384,896] custom-call(f32[16384,2304] "
            "%fusion.7, f32[64,2304,896] %params__l3_we_up__.1)", 80, 90)]
    trace = {"window": (0, 100 * MS),
             "device_ops": {"/device:TPU:0": [(n, s * MS, e * MS)
                                              for n, s, e in ops]},
             "spans": {"bench.window": [(0, 100 * MS)],
                       "bench.decode": [(10 * MS, 30 * MS),
                                        (50 * MS, 70 * MS)]},
             "moe": {"markers": markers}}
    calls = [run.Call("decode", 0.1, 0.2, np.zeros(16), np.ones(16)),
             run.Call("prefill", 0.2, 0.3, np.zeros(2), np.ones(2)),
             run.Call("decode", 0.3, 0.4, np.zeros(16), np.ones(16))]
    cell = mellum()
    win = run.Window(0.0, 1.0, [], calls, 0, trace, 1.0)
    return run.RunRecord(cell, run.peaks_for("TPU v5 lite"), win)


MARKERS = [(15 * MS, 200, 128), (55 * MS, 230, 128), (85 * MS, 250, 2048)]


def read(name, record):
    return mellum().readers[name].read(record)


def test_moe_decode_share_by_hand():
    # expert ops 8 + 8 ms of 14 + 16 ms of device time in the decode spans
    assert read("moe_decode_share", hand_record(MARKERS)) == \
        pytest.approx(100 * 16 / 30)


def test_moe_roofline_by_hand():
    need = (200 + 230) * 3 * 2304 * 896 * 4 + 2 * 128 * 2 * 2304 * 4
    assert read("moe_roofline", hand_record(MARKERS)) == \
        pytest.approx(100 * need / 819e9 / 0.016)


def test_experts_hit_share_by_hand():
    # median of 200 / 256 and 230 / 256
    assert read("experts_hit_share", hand_record(MARKERS)) == \
        pytest.approx(100 * 215 / 256)


def test_expert_ops_are_named_by_the_expert_weights_they_read():
    assert moe_trace.is_expert_op(
        "%fusion.52 = f32[64,16,896] fusion(f32[64,2304,896] "
        "%params__l3_we_up__.1, f32[16,2304] %multiply_multiply_fusion)")
    assert moe_trace.is_expert_op("%ragged-dot-none.5 = f32[128,896] "
                                  "custom-call(...)")
    assert not moe_trace.is_expert_op(
        "%multiply_reduce_fusion.8 = (f32[16], f32[16,2304]) fusion("
        "f32[98304,2304] %params__embed__.1, s32[16] %clamp_bitcast_fusion)")


def test_calls_pair_with_the_marker_inside_their_span():
    calls = moe_trace.decode_calls(hand_record(MARKERS))
    assert calls == [((10 * MS, 30 * MS), 200, 128),
                     ((50 * MS, 70 * MS), 230, 128)]


@pytest.mark.parametrize("name", ["moe_decode_share", "moe_roofline",
                                  "experts_hit_share"])
def test_a_program_without_markers_reads_nothing(name):
    """A program that writes no ``moe.counts`` (the parent of this
    configuration, or a dense model) gives these metrics nothing."""
    assert read(name, hand_record([])) is None
    untraced = hand_record(MARKERS)
    untraced.window.trace = None
    assert read(name, untraced) is None


# --------------------------------------------------------------------------- #
# a tiny cell of the family on the CPU
# --------------------------------------------------------------------------- #

def tiny_layout():
    return run.Layout(os.path.join(MOE_FIXTURE, "BENCHMARK.json"),
                      roots=[MOE_FIXTURE, FIXTURE, run.BENCH])


def test_a_tiny_moe_cell_is_correct_and_its_control_is_not():
    """Served through ``build_lm_serving`` as the real cell is, past the
    window of its sliding layers: the program passes the check, and the
    bfloat16 control on the same sample fails it."""
    cell = tiny_layout().cell("tiny_moe.decode")
    assert cell.cache_cap > cell.cfg["sliding_window"]
    session = run.build(cell, 2**33 + 5)
    win = run.serve(session, seconds=2.0, traced=False)
    program = run.check(cell, session.params, win, 2**33 + 5)
    control = run.check(cell, session.params, win, 2**33 + 5, control=True)
    assert run.passed(program), program
    assert not run.passed(control), control
    rows = session.engine.metrics.expert_rows
    assert rows is not None and rows.shape == (4, 8)
    assert (rows.sum(axis=1) == rows.sum() // 4).all()
