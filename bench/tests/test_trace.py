"""The reduction from a trace's events to the per-layer metrics: on a
hand-made event list whose answers are known, and on events recorded from
chip runs (bench/tests/data)."""
import json
import os

import pytest

from bench import trace
from bench.manifest import metric_reader

DATA = os.path.join(os.path.dirname(__file__), "data")


def _hand_made():
    # names: 0 window, 1 round, 2 loss_fetch, 3 round program, 4 fusion,
    # 5 all-reduce, 6 batch stack
    names = ["bench.window", "bench.round", "bench.loss_fetch", "jit_round",
             "fusion.1", "all-reduce.2", "concatenate"]
    host = [[0, 0, 1000], [1, 0, 480], [1, 480, 520], [2, 300, 180],
            [2, 800, 200]]
    # two round programs, [100, 400) and [600, 900); inside them a fusion
    # and an all-reduce that overlaps it by 20; a stack op between them
    ops = [[4, 100, 200], [5, 280, 100], [4, 600, 200], [5, 790, 110],
           [6, 450, 50]]
    modules = [[3, 100, 300], [3, 600, 300]]
    return {"names": names, "host": host,
            "devices": {"0": {"ops": ops, "modules": modules}}}


def test_hand_made_events():
    s = trace.summarize(_hand_made())
    assert s["window_s"] == pytest.approx(1000e-9)
    # busy: [100, 380) + [450, 500) + [600, 900) = 280 + 50 + 300
    assert s["busy_s"]["0"] == pytest.approx(630e-9)
    assert s["rounds"] == 2 and s["round_program"] == "jit_round"
    assert s["round_busy_s"] == pytest.approx((280 + 300) / 2 * 1e-9)
    # between the programs: [400, 600) minus the stack op's 50
    assert s["round_gap_idle_s"] == pytest.approx(150e-9)
    assert s["device_ops"][0] == ["fusion.1", pytest.approx(400e-9)]
    # longest idle: [0, 100) outside any round, then [380, 450) in the
    # first round's loss fetch
    assert s["idle_gaps"][0] == ["round", pytest.approx(100e-9)]
    assert ["loss_fetch", pytest.approx(70e-9)] in s["idle_gaps"]


def test_metric_readers_on_hand_made_events():
    rec = {"trace": trace.summarize(_hand_made()), "tokens_per_s": 1e6,
           "flops_per_token": 1e6, "chips": 1, "peak_flops": 1e13}
    assert metric_reader("round_device_ms")(rec) == pytest.approx(290e-6)
    assert metric_reader("host_gap_ms")(rec) == pytest.approx(150e-6)
    assert metric_reader("device_idle_pct")(rec) == pytest.approx(37.0)
    assert metric_reader("mfu_pct")(rec) == pytest.approx(10.0)


def _recorded(name):
    with open(os.path.join(DATA, name + ".events.json")) as f:
        return json.load(f)


def test_recorded_one_chip_trace():
    """Three rounds of a `--trace 1` run of starcoder2-3b-L1.h2.1chip on a
    TPU v5e, cut by `trace.cut`."""
    s = trace.summarize(_recorded("starcoder2-3b-L1.h2.1chip"))
    assert s["rounds"] == 3
    assert s["round_program"].startswith("jit_round_fn")
    rec = {"trace": s}
    assert metric_reader("round_device_ms")(rec) == pytest.approx(
        557.1837026666666, rel=1e-9)
    assert metric_reader("host_gap_ms")(rec) == pytest.approx(4.7467465,
                                                             rel=1e-9)
    assert metric_reader("device_idle_pct")(rec) == pytest.approx(
        100 * (1 - 1.671565125 / 1.683105281), rel=1e-9)
    # the breakdown: AdamW's fused update over [2, 247 M] takes most
    # self time; the longest idle gaps fall in the loss fetch
    assert s["device_ops"][0][0].startswith("%fusion.72 = (f32[2,246958080]")
    assert s["device_ops"][0][1] == pytest.approx(0.129365345, rel=1e-9)
    assert s["idle_gaps"][0] == ["loss_fetch", pytest.approx(0.00175347)]
