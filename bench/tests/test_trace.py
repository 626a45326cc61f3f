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


HLO = """HloModule jit_pinned, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %all-reduce.9 = f32[8]{0} all-reduce(%p), replica_groups={{0,1}}, to_apply=%add
}

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.1 = f32[] add(%a, %b)
}

%async_computation (q: f32[8]) -> f32[16] {
  %q = f32[8]{0} parameter(0)
  ROOT %all-gather.2 = f32[16]{0} all-gather(%q), dimensions={0}
}

ENTRY %main.3 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %fusion.5 = f32[8]{0:T(1024)} fusion(%x), kind=kLoop, calls=%fused_computation.1
  %all-gather-start = (f32[8]{0}, f32[16]{0}) all-gather-start(%x), dimensions={0}
  %all-gather-done = f32[16]{0} all-gather-done(%all-gather-start)
  %async-start.1 = ((f32[8]{0}), f32[16]{0}, u32[]) async-start(%x), calls=%async_computation
  %all-reduce.7 = (f32[]{:T(128)}, f32[]{:T(128)}) all-reduce(%a, %b), to_apply=%add
  %fusion.6 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.2
  %while.1 = (f32[8]{0}) while(%t), condition=%cond, body=%body
  ROOT %copy.1 = f32[8]{0} copy(%fusion.5), metadata={op_name="jit(f)/all-reduce(x)"}
}"""


def test_collective_ops_by_opcode():
    assert trace.collective_ops(HLO) == {
        "all-reduce.9", "all-gather.2", "fusion.5", "all-gather-start",
        "all-gather-done", "async-start.1", "all-reduce.7"}
    assert trace.collective_ops("HloModule m") == set()


def _hand_made_mesh():
    # names: 0 window, 1 round, 2 round program, then device ops
    names = ["bench.window", "bench.round", "jit_pinned", "while.1",
             "fusion.1", "all-reduce.5", "all-reduce.4", "fusion.2",
             "all-gather.4", "fusion.3"]
    host = [[0, 0, 1000], [1, 0, 500]]
    modules = [[2, 100, 300]]
    # device 0: a loop [100, 300) around fusion.1 and, after it, a scalar
    # all-reduce; then the sync's all-reduce, 20 of it under fusion.2, and
    # its all-gather alone
    ops0 = [[3, 100, 200], [4, 110, 180], [5, 292, 6], [6, 300, 60],
            [7, 340, 40], [8, 380, 15]]
    # device 1: a longer all-reduce, wholly under fusion.3
    ops1 = [[6, 300, 90], [9, 300, 90]]
    return {"names": names, "host": host,
            "devices": {"0": {"ops": ops0, "modules": modules},
                        "1": {"ops": ops1, "modules": modules}}}


def test_collective_and_exposed_time_on_hand_made_events():
    coll = frozenset({"all-reduce.4", "all-reduce.5", "all-gather.4"})
    s = trace.summarize(_hand_made_mesh(), coll)
    # device 0: 6 + 60 + 15 = 81, exposed 6 (the loop is not work) + 40
    # + 15 = 61; device 1: 90, exposed 0.  Each on its largest device
    assert s["collective_s"] == pytest.approx(90e-9)
    assert s["collective_device"] == "1"
    assert s["collective_exposed_s"] == pytest.approx(61e-9)
    assert s["exposed_device"] == "0"
    rec = {"trace": s}
    assert metric_reader("sync_collective_ms")(rec) == pytest.approx(90e-6)
    assert metric_reader("sync_exposed_ms")(rec) == pytest.approx(61e-6)
    # without the compiled round's collectives nothing is read
    none = trace.summarize(_hand_made_mesh())
    assert none["collective_s"] is None
    assert metric_reader("sync_collective_ms")({"trace": none}) is None
    assert metric_reader("sync_exposed_ms")({"trace": none}) is None


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


def test_recorded_four_chip_trace():
    """Two rounds of a `--trace 1` run of starcoder2-3b-L1.h2.4chip on a
    four-chip TPU v5e host, with the collectives of its compiled round and
    their parts by the program's scopes (bench/tests/record_trace.py)."""
    from bench import scopes

    events = _recorded("starcoder2-3b-L1.h2.4chip")
    assert sorted(events["devices"]) == ["0", "1", "2", "3"]
    # the metrics read the collectives outside `telemetry`, as
    # scopes.sync_collectives picks them from the compiled round
    sync = frozenset(op for op in events["collectives"]
                     if events["parts"][op] != "telemetry")
    s = trace.summarize(events, sync)
    assert s["rounds"] == 2
    rec = {"trace": s}
    coll = metric_reader("sync_collective_ms")(rec)
    exposed = metric_reader("sync_exposed_ms")(rec)
    assert coll == pytest.approx(25.422947, rel=1e-9)
    assert s["collective_device"] == "2"
    # with the divergence's all-reduce, every collective of the round
    every = trace.summarize(events, frozenset(events["collectives"]))
    assert every["collective_s"] * 1e3 == pytest.approx(42.785468, rel=1e-9)
    # the sync blocks: nothing runs beside its collectives
    assert exposed == pytest.approx(coll, rel=1e-9)
    assert 0 < exposed <= coll < metric_reader("round_device_ms")(rec)
    # against the program's scopes, on the first device: the sync's
    # all-gather is in `sync`; its reduce-scatter, lowered as an
    # all-reduce, lost its op_name (other); the divergence's all-reduce of
    # the parameters is in `telemetry`
    ops, rounds = scopes.round_ops(events)
    by_part = {}
    for op, t in ops.items():
        if op in events["collectives"]:
            part = events["parts"][op]
            by_part[part] = by_part.get(part, 0.0) + t / rounds
    assert by_part["sync"] == pytest.approx(0.0079432925, rel=1e-6)
    assert by_part["other"] == pytest.approx(0.017364549, rel=1e-6)
    assert by_part["telemetry"] == pytest.approx(0.0173587265, rel=1e-6)
    assert by_part["sync"] + by_part["other"] == pytest.approx(
        coll / 1e3, rel=5e-3)
