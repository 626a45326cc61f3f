"""The round's parts, its scopes and the engine's spans: the scopes the
program puts in its compiled round, the reduction of a trace by part, by
scope and by engine span (bench/scopes.py) on a hand-made event list and on
events recorded from a chip run (bench/tests/data), the readers of the
parts, a scope and reader added as new files only, the engine's spans in a
CPU profile, and its compile counter."""
import contextlib
import json
import os
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, manifest, scopes, trace
from bench.tests.conftest import TINY_CELLS

DATA = os.path.join(os.path.dirname(__file__), "data")
NAMED = {"forward", "backward", "optimizer", "sync", "telemetry"}


def _system(root, name, seed=11):
    from bench.system import System
    sys_ = System(manifest.load_cell(root, name))
    return sys_, sys_.init(seed)


def _round_text(sys_, state, t):
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=x.sharding), state)
    return sys_.eng.compiled_round(shapes, t, sys_.get_h(t),
                                   sys_.lr_fn).as_text()


@pytest.mark.parametrize("op_name, part", [
    ("jit(round_fn)/while/body/closed_call/cond/branch_1_fun/vmap(grad)/"
     "jvp()/while/body/closed_call/dot_general", "forward"),
    ("jit(round_fn)/while/body/closed_call/cond/branch_1_fun/vmap(grad)/"
     "transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/dot_general", "backward"),
    ("jit(f)/grad/jvp()/bhgqk,bkhd->bqhgd/transpose", "forward"),
    ("jit(round_fn)/while/body/closed_call/cond/branch_1_fun/optimizer/mul",
     "optimizer"),
    ("jit(round_fn)/sync/reduce_sum", "sync"),
    ("jit(round_fn)/telemetry/sqrt", "telemetry"),
    ("jit(f)/sync/telemetry/add", "telemetry"),
    ("jit(round_fn)/while/body/dynamic_slice", "other"),
    ("jit(resync)/syncing/add", "other"),
    ("jit(round_fn)/cond/broadcast_in_dim;cond/optimizer/reshape",
     "optimizer"),
    ("jit(round_fn)/sync/add;jit(round_fn)/telemetry/sqrt", "sync"),
])
def test_part_of_an_op_path(op_name, part):
    assert scopes.part_of(op_name) == part


@pytest.mark.parametrize("op_name, names", [
    ("jit(step)/grad/vmap(jvp(moe))/tanh", {"step", "grad", "moe"}),
    ("jit(round_fn)/while/body/vmap(grad)/transpose(jvp())/checkpoint/"
     "rematted_computation/dot_general",
     {"round_fn", "while", "body", "grad", "checkpoint",
      "rematted_computation"}),
    ("jit(f)/sync/add;jit(f)/telemetry/sqrt", {"f", "sync", "telemetry"}),
    ("reduce_sum", set()),
])
def test_scope_names_of_an_op_path(op_name, names):
    assert scopes.scope_names(op_name) == names


def test_op_parts_of_hlo_text():
    text = """HloModule jit_round_fn
ENTRY %main.1 (p: f32[4]) -> f32[4] {
  %fusion.72 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(round_fn)/optimizer/add" stack_frame_id=6}
  ROOT %wrapped_reduce-window.3 = f32[4]{0} fusion(%fusion.72), metadata={op_name="jit(round_fn)/vmap(grad)/transpose(jvp())/mul"}
  %copy.1 = f32[4]{0} copy(%p)
}"""
    assert scopes.op_parts(text) == {"fusion.72": "optimizer",
                                     "wrapped_reduce-window.3": "backward"}
    assert scopes.instruction(
        "%fusion.72 = (f32[2,246958080]{1,0}) fusion(...)") == "fusion.72"
    assert scopes.instruction("dot.134") == "dot.134"


def test_sync_collectives_leave_out_telemetry():
    text = """HloModule jit_pinned
ENTRY %main.1 (p: f32[4]) -> f32[4] {
  %all-reduce.2 = f32[4]{0} all-reduce(%p), to_apply=%add, metadata={op_name="jit(pinned)/telemetry/reduce_sum"}
  %all-reduce.4 = f32[4]{0} all-reduce(%p), to_apply=%add
  %all-gather.4 = f32[16]{0} all-gather(%p), dimensions={0}, metadata={op_name="jit(pinned)/sync/all_gather"}
  ROOT %fusion.3 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(pinned)/telemetry/sqrt"}
}"""
    assert trace.collective_ops(text) == {"all-reduce.2", "all-reduce.4",
                                          "all-gather.4"}
    assert scopes.sync_collectives(text) == {"all-reduce.4", "all-gather.4"}


@pytest.mark.parametrize("name", sorted(TINY_CELLS))
def test_compiled_round_names_every_part(tiny_root, name):
    sys_, state = _system(tiny_root, name)
    state, t, _ = harness.first_rounds(sys_, state, 11)
    found = set(scopes.op_parts(_round_text(sys_, state, t)).values())
    assert NAMED <= found, found


@contextlib.contextmanager
def _no_scope(name):
    yield


@pytest.mark.parametrize("name", sorted(TINY_CELLS))
def test_scopes_leave_the_round_bitwise(tiny_root, monkeypatch, name):
    scoped, s0 = _system(tiny_root, name)
    s_scoped, t, read_scoped = harness.first_rounds(scoped, s0, 11)
    monkeypatch.setattr(jax, "named_scope", _no_scope)
    bare, s0 = _system(tiny_root, name)
    s_bare, _, read_bare = harness.first_rounds(bare, s0, 11)
    assert set(scopes.op_parts(_round_text(bare, s_bare, t)).values()) \
        == {"other"}
    assert read_scoped["losses"] == read_bare["losses"]
    for a, b in zip(jax.tree.leaves(s_scoped), jax.tree.leaves(s_bare)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_compile_counter_grows_with_new_programs_only(tiny_root):
    sys_, state = _system(tiny_root, "tiny-decoder.h2")
    eng, t = sys_.eng, sys_.t0
    assert sys_.get_h(t) == 2
    stats = eng.compile_stats()
    assert stats["compile_s"] == 0.0 and stats["compiled_at"] == []
    for h_round, new in ((2, True), (2, False), (3, True), (4, False),
                         (1, True), (2, False)):
        before = eng.compile_stats()
        state, m = eng.run_round(state, t, h_round, sys_.lr_fn)
        float(m["loss"])
        after = eng.compile_stats()
        if new:
            assert after["compile_s"] > before["compile_s"]
            assert after["compiled_at"][:-1] == before["compiled_at"]
            hp = 1 if h_round == 1 else 1 << (h_round - 1).bit_length()
            assert after["compiled_at"][-1] == (t, (hp, sys_.workers))
        else:
            assert after["compile_s"] == before["compile_s"]
            assert after["compiled_at"] == before["compiled_at"]
        t += h_round
    assert [k for _, k in eng.compile_stats()["compiled_at"]] == [
        (2, 2), (4, 2), (1, 2)]


def test_engine_spans_nest_in_the_dispatch(tiny_root):
    """A few rounds under the profiler on the CPU: the engine's spans are
    kept beside the harness's, each inside a `bench.dispatch`, and the
    first round, which compiles, is named so."""
    sys_, state = _system(tiny_root, "tiny-decoder.h2")
    d = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    t = sys_.t0
    for _ in range(3):              # a window of no time runs one round
        state, win = harness.window(sys_, state, t, 0.0)
        t = win["t_end"]
    jax.profiler.stop_trace()
    events = scopes.engine_spans(trace.compact(d), d)
    names = events["names"]
    spans = [(names[n], s, s + du) for n, s, du in events["host"]]
    dispatch = [(s, e) for n, s, e in spans if n == "bench.dispatch"]
    engine = [(n, s, e) for n, s, e in spans if n.startswith("repro.")]
    count = {n: sum(1 for m, _, _ in engine if m == n)
             for n in ("repro.engine.args", "repro.engine.launch",
                       "repro.engine.compile")}
    assert count == {"repro.engine.args": 3, "repro.engine.launch": 2,
                     "repro.engine.compile": 1}
    for n, s, e in engine:
        assert any(ds <= s and e <= de for ds, de in dispatch), n
    first = min(engine, key=lambda x: x[1])
    assert first[0] == "repro.engine.args"
    assert min((x for x in engine if x[0] != "repro.engine.args"),
               key=lambda x: x[1])[0] == "repro.engine.compile"


def _hand_made():
    # a window [0, 1000) of two rounds; the round program runs [100, 400)
    # and [600, 900); a stack op runs between them at [450, 500)
    names = ["bench.window", "bench.dispatch", "repro.engine.args",
             "bench.batch_fetch", "repro.engine.launch", "jit_round",
             "%fusion.1 = (f32[4]) fusion()", "%fusion.2 = f32[4] fusion()",
             "%while.3 = (f32[4]) while()", "%copy.4 = f32[4] copy()",
             "concatenate.5"]
    host = [[0, 0, 1000],
            [1, 30, 70], [2, 40, 50], [3, 60, 10], [4, 90, 10],
            [1, 430, 165], [2, 440, 120], [3, 470, 10], [4, 560, 30]]
    # round 1: a while [100, 300) around fusion.1 [120, 220), fusion.2
    # [300, 380); round 2: the while [600, 800) around fusion.1 [650,
    # 700), copy.4 [800, 900)
    ops = [[8, 100, 200], [6, 120, 100], [7, 300, 80], [10, 450, 50],
           [8, 600, 200], [6, 650, 50], [9, 800, 100]]
    modules = [[5, 100, 300], [5, 600, 300]]
    return {"names": names, "host": host,
            "devices": {"0": {"ops": ops, "modules": modules}}}


def test_split_and_engine_idle_of_hand_made_events():
    events = _hand_made()
    names = {"fusion.1": "jit(r)/grad/transpose(jvp(attn))/mul",
             "fusion.2": "jit(r)/optimizer/add", "copy.4": "jit(r)/sync/copy"}
    summ = scopes.summarize(events, names)
    got = summ["parts"]
    # self times: the while net of the fusion inside it, 100 then 150
    want = {"forward": 0, "backward": (100 + 50) / 2, "optimizer": 80 / 2,
            "sync": 100 / 2, "telemetry": 0, "other": (100 + 150) / 2}
    assert got == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    s = trace.summarize(events)
    assert sum(got.values()) == pytest.approx(s["round_busy_s"])
    # inclusive: every name on an op's stack; a part scope with no op is 0
    assert summ["scopes"] == pytest.approx({
        "grad": 75e-9, "attn": 75e-9, "optimizer": 40e-9, "sync": 50e-9,
        "telemetry": 0.0, "r": 165e-9})
    idle = scopes.engine_idle(events)
    # args: [40, 90) all idle; [440, 560), the batch fetch inside it, less
    # the stack op's 50.  launch: [90, 100) and [560, 590), all idle
    assert idle == pytest.approx({"repro.engine.args": (50 + 70) / 2e9,
                                  "repro.engine.launch": (10 + 30) / 2e9})
    assert sum(idle.values()) <= s["round_gap_idle_s"]


def _recorded(name):
    with open(os.path.join(DATA, name + ".scopes.json")) as f:
        return json.load(f)


def test_recorded_one_chip_split():
    """Three rounds of a `--trace 1` run of starcoder2-3b-L1.h2.1chip on a
    TPU v5e with the engine's spans, cut by `trace.cut`, beside the
    `op_name` of each op in the cut from the compiled round and the
    harness's split of the whole window (bench/tests/record_scopes.py)."""
    rec = _recorded("starcoder2-3b-L1.h2.1chip")
    events, want = rec["events"], rec["trace"]
    s = trace.summarize(events)
    assert s["rounds"] == 3
    got = scopes.summarize(events, rec["op_names"])
    parts = got["parts"]
    assert sum(parts.values()) == pytest.approx(s["round_busy_s"], rel=1e-4)
    assert all(parts[p] > 0 for p in NAMED), parts
    # the rounds of the cut split as the whole window did
    assert parts == pytest.approx(want["parts"], rel=5e-3)
    assert set(got["scopes"]) == set(want["scopes"])
    for name, t in want["scopes"].items():
        assert got["scopes"][name] == pytest.approx(t, rel=5e-3, abs=1e-6)
    # the part scopes hold no other, so `grad` is forward and backward
    assert got["scopes"]["grad"] == pytest.approx(
        parts["forward"] + parts["backward"], rel=1e-9)
    assert got["scopes"]["telemetry"] >= parts["telemetry"]
    # AdamW over the flat [2, 247 M] bucket is one fusion of the optimizer,
    # about 43 ms a round
    assert scopes.part_of(rec["op_names"]["fusion.60"]) == "optimizer"
    assert parts["optimizer"] == pytest.approx(0.043, rel=0.1)
    idle = scopes.engine_idle(events)
    assert set(idle) == {"repro.engine.args", "repro.engine.launch"}
    assert sum(idle.values()) <= s["round_gap_idle_s"]


def _toy_round(scope):
    """The compiled text of a small jitted step: a vmapped gradient in
    `grad`, whose loss puts its matmul under `scope("moe")`, then an update
    in `optimizer`."""
    def loss(w, x):
        with scope("moe"):
            h = jnp.tanh(x @ w)
        return jnp.sum(h * h)

    def step(w, x):
        with jax.named_scope("grad"):
            g = jax.vmap(jax.grad(loss), in_axes=(None, 0))(w, x)
        with jax.named_scope("optimizer"):
            return w - 0.1 * jnp.mean(g, 0)

    w, x = jnp.ones((8, 8)), jnp.ones((4, 3, 8))
    return jax.jit(step).lower(w, x).compile().as_text()


def _one_round_of(ops, dur=10):
    """Events of a window holding one run of the round program, in which
    each of `ops` runs for `dur` ns after the other."""
    names = ["bench.window", "jit_step", *ops]
    return {"names": names, "host": [[0, 0, 10 + dur * len(ops) + 10]],
            "devices": {"0": {
                "ops": [[i + 2, 10 + dur * i, dur] for i in range(len(ops))],
                "modules": [[1, 10, dur * len(ops)]]}}}


def test_a_scope_inside_grad_is_read_inclusively_and_leaves_the_parts():
    named = scopes.op_names(_toy_round(jax.named_scope))
    bare = scopes.op_names(_toy_round(lambda name: contextlib.nullcontext()))
    assert named.keys() == bare.keys()
    events = _one_round_of(sorted(named))
    with_moe, without = (scopes.summarize(events, named),
                         scopes.summarize(events, bare))
    assert with_moe["parts"] == without["parts"]
    assert with_moe["parts"]["forward"] > 0
    assert with_moe["parts"]["backward"] > 0
    in_moe = [op for op, n in named.items() if "moe" in n]
    assert in_moe and "moe" not in without["scopes"]
    assert with_moe["scopes"]["moe"] == pytest.approx(len(in_moe) * 1e-8)
    # inclusive: every op of `moe` is an op of `grad` too
    assert with_moe["scopes"]["grad"] == pytest.approx(
        with_moe["parts"]["forward"] + with_moe["parts"]["backward"])
    assert with_moe["scopes"]["moe"] < with_moe["scopes"]["grad"]
    assert with_moe["scopes"]["sync"] == with_moe["scopes"]["telemetry"] == 0


def test_a_new_scope_and_reader_are_files_only(tiny_root, tmp_path,
                                               monkeypatch):
    """A scope new to the program and a reader file new to metrics/, named
    in BENCHMARK.json, give a reported metric: nothing of the benchmark's
    code is edited."""
    import bench.metrics
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        man = json.load(f)
    man["per_layer"].append(
        {"name": "moe_ms", "unit": "ms", "better": "lower",
         "source": "device_trace", "layer": "local step",
         "moves": "tokens_per_s"})
    with open(path, "w") as f:
        json.dump(man, f)
    readers = tmp_path / "readers"
    readers.mkdir()
    (readers / "moe_ms.py").write_text(
        "def read(rec):\n"
        "    t = rec['trace']\n"
        "    if t is None or 'moe' not in t['scopes']:\n"
        "        return None\n"
        "    return t['scopes']['moe'] * 1e3\n")
    monkeypatch.setattr(bench.metrics, "__path__",
                        [*bench.metrics.__path__, str(readers)])

    named = scopes.op_names(_toy_round(jax.named_scope))
    events = _one_round_of(sorted(named))
    record = {"compiles_in_window": 0,
              "trace": {**trace.summarize(events),
                        **scopes.summarize(events, named)}}
    cell = manifest.load_cell(tiny_root, "tiny-decoder.h2")
    try:
        got = harness.read_metrics(cell, record, True)
        n_moe = sum("moe" in n for n in named.values())
        assert got["moe_ms"] == {"value": pytest.approx(n_moe * 1e-5),
                                 "unit": "ms"}
        assert "moe_ms" not in harness.read_metrics(
            cell, {**record, "trace": None}, True)
    finally:
        sys.modules.pop("bench.metrics.moe_ms", None)


@pytest.mark.parametrize("part", ["forward", "backward", "optimizer",
                                  "telemetry", "other"])
def test_part_readers(part):
    read = manifest.metric_reader(part + "_ms")
    assert read({"trace": None}) is None
    parts = dict.fromkeys(scopes.PARTS, 0.0)
    parts[part] = 0.0125
    assert read({"trace": {"parts": parts}}) == pytest.approx(12.5)
