"""One run of one cell: set-up, the measured window, and the comparison
with the reference that decides `correct`.

Set-up builds the program's RoundEngine, makes its state and the input pool
on the device from the seed, and runs the cell's first rounds through the
window's own call (`run_round`) and feed: they compile the round program
(or load it from the persistent cache), warm everything the window uses,
and are the rounds the comparison reads.  The window then drives
`run_round` round after round as `launch/train.py` does, H from the
program's QSR schedule and each round's loss read to the host, until
`seconds` have passed.  Once the window has closed and the peak memory has
been read, the program's state is freed and the reference runs the
compared rounds from the same seed.
"""
from __future__ import annotations

import gc
import math
import shutil
import sys
import tempfile
import time

import jax
import numpy as np

from bench import compare, flops, manifest, peaks, scopes, trace
from bench.reference import common as C
from bench.reference.train import Reference

GIB = 2 ** 30


class ChipError(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def check_chips(chips: int) -> list:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise ChipError(f"needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise ChipError(f"the cell needs {chips} chips, JAX sees "
                        f"{len(devices)}")
    return devices


def enable_compile_cache() -> str:
    """The program's persistent compilation cache (inside the checkout, or
    JAX_COMPILATION_CACHE_DIR), kept for every program however fast it
    compiled, so that only a cell's first run in a checkout compiles."""
    from repro.launch.compile_cache import enable_compile_cache as enable
    where = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


def span(name: str):
    return jax.profiler.TraceAnnotation(name)


def compared_rounds(sys_) -> int:
    """Rounds whose steps the comparison covers: the fewest that hold the
    traffic's `compare_steps` steps."""
    h = sys_.get_h(sys_.t0)
    return max(1, math.ceil(sys_.cell.traffic["compare_steps"] / h))


def first_rounds(sys_, state, seed: int):
    """Set-up's rounds through `run_round` from the traffic's start step:
    at least two, and as many as the comparison covers.  Returns (state,
    next step, the program's readings)."""
    n_cmp = compared_rounds(sys_)
    t = sys_.t0
    read = {"losses": []}
    for r in range(max(2, n_cmp)):
        h = sys_.get_h(t)
        state, m = sys_.eng.run_round(state, t, h, sys_.lr_fn)
        t += h
        loss = float(m["loss"])
        if r < n_cmp:
            read["losses"].append(loss)
        if r == 0:
            read["grad"] = sys_.grad_norms(state)
        if r == n_cmp - 1:
            read["change"] = sys_.change_norms(state, seed)
    return state, t, read


def window(sys_, state, t: int, seconds: float):
    """Rounds until `seconds` have passed, as launch/train.py drives them:
    one scalar, the loss, read to the host a round.  Returns (state,
    stats); stats' "counters" are the scalars of the last round's metrics,
    read once the clock has stopped."""
    steps = rounds = bad = 0
    with span("bench.window"):
        t_start = time.perf_counter()
        while True:
            with span("bench.round"):
                h = sys_.get_h(t)
                with span("bench.dispatch"):
                    state, m = sys_.eng.run_round(state, t, h, sys_.lr_fn)
                t += h
                with span("bench.loss_fetch"):
                    loss = float(m["loss"])
            steps, rounds = steps + h, rounds + 1
            bad += 0 if math.isfinite(loss) else h
            if time.perf_counter() - t_start >= seconds:
                break
        jax.block_until_ready(state)
        elapsed = time.perf_counter() - t_start
    counters = {k: float(v) for k, v in m.items() if np.ndim(v) == 0}
    return state, {"seconds": elapsed, "steps": steps, "rounds": rounds,
                   "nonfinite_steps": bad, "t_end": t, "counters": counters}


def reference(cell, devices, *, num=C.FLOAT32, faults=()) -> Reference:
    return Reference(cell.model, cell.conf, cell.traffic,
                     cell.traffic["workers"], num=num, faults=faults,
                     devices=devices)


def reference_readings(cell, seed: int, ref: Reference) -> dict:
    """The reference's readings of the compared rounds, from the seed: it
    makes the weights and the pool again and runs the rounds itself."""
    from bench import inputs
    from bench.reference.train import qsr_h
    shapes = cell.model.param_shapes(cell.conf)
    params0 = jax.jit(lambda k: C.init_params(shapes, k))(
        C.seed_key(seed, C.WEIGHTS))
    pool = inputs.make_pool(cell.model.INPUT, cell.conf, cell.traffic,
                            cell.traffic["workers"], seed)
    t0 = cell.traffic["start_step"]
    rounds = max(1, math.ceil(cell.traffic["compare_steps"]
                              / qsr_h(cell.traffic["schedule"], t0)))
    return ref.run(params0, pool, t0=t0, rounds=rounds)


def compiled_round(sys_, state, t: int):
    """The compiled round program the window drives.  A program the window
    ran is found in JAX's caches, not compiled anew."""
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=x.sharding), state)
    return sys_.eng.compiled_round(shapes, t, sys_.get_h(t), sys_.lr_fn)


def program_bytes(compiled) -> dict:
    """The memory_analysis of a compiled program, per device: arguments,
    outputs not aliased to them, temporaries and code."""
    ma = compiled.memory_analysis()
    return {"argument": ma.argument_size_in_bytes,
            "output": ma.output_size_in_bytes,
            "alias": ma.alias_size_in_bytes,
            "temp": ma.temp_size_in_bytes,
            "code": ma.generated_code_size_in_bytes}


def peak_bytes(sys_, state, compiled) -> tuple[int, dict]:
    """Peak bytes on the fullest device while a round runs.  The
    allocator's `peak_bytes_in_use` does not count a program's
    temporaries, so each device's peak is the larger of it and of what
    stays resident beside the round program (in use between rounds, less
    the state, which is the program's argument) plus the program's own
    footprint from its memory_analysis.  Returns (bytes, the readings)."""
    stats = {d: d.memory_stats() or {} for d in sys_.devices}
    prog = program_bytes(compiled)
    footprint = (prog["argument"] + prog["output"] - prog["alias"]
                 + prog["temp"] + prog["code"])
    state_on = {d: 0 for d in sys_.devices}
    for leaf in jax.tree.leaves(state):
        for shard in leaf.addressable_shards:
            if shard.device in state_on:
                state_on[shard.device] += shard.data.nbytes
    per_device = {}
    for d, st in stats.items():
        resident = st.get("bytes_in_use", 0) - state_on[d]
        per_device[str(d.id)] = {
            "peak_bytes_in_use": st.get("peak_bytes_in_use", 0),
            "resident_beside_program": resident,
            "during_round": resident + footprint}
    peak = max(max(v["peak_bytes_in_use"], v["during_round"])
               for v in per_device.values())
    return peak, {"program": prog, "devices": per_device}


def read_metrics(cell, record: dict, traced: bool) -> dict:
    """The cell's end-to-end metrics from the record, or, traced, its
    per-layer metrics, each by its reader (metrics/<name>.py); a metric
    whose reader finds nothing to read is left out."""
    out = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        value = (manifest.metric_reader(m["name"])(record) if traced
                 else record[m["name"]])
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(root: str, workload: str, seed: int, seconds: float,
             traced: bool, *, t_process: float,
             require_tpu: bool = True) -> dict:
    """One run; returns the result object the command prints."""
    cell = manifest.load_cell(root, workload)
    devices = (check_chips(cell.chips) if require_tpu
               else jax.devices()[:cell.chips])
    from repro.kernels import ops as kops
    from bench.system import System
    log(f"cell {workload} seed {seed} on {devices[0].device_kind} "
        f"x{len(jax.devices())}; kernel backend {kops.get_backend()}")

    t_init = time.perf_counter()
    sys_ = System(cell)
    state = sys_.init(seed)
    setup_init_s = time.perf_counter() - t_init
    state, t, prog = first_rounds(sys_, state, seed)
    jax.block_until_ready(state)
    compiles0 = sys_.eng.compile_stats()["compiles"]
    h0 = sys_.get_h(t)

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    if traced:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # spans and device events only
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    setup_s = time.perf_counter() - t_process
    state, win = window(sys_, state, t, seconds)
    if traced:
        jax.profiler.stop_trace()
    stats = sys_.eng.compile_stats()
    compiles = stats["compiles"] - compiles0
    if compiles:
        raise RuntimeError(f"{compiles} round programs compiled inside the "
                           "window: H left its warmed bucket")
    if sys_.get_h(win["t_end"]) != h0:
        log(f"H moved from {h0} to {sys_.get_h(win['t_end'])} by the end")
    t_mem = time.perf_counter()
    compiled = compiled_round(sys_, state, t)
    peak, mem = peak_bytes(sys_, state, compiled)
    if traced:
        text = compiled.as_text()
        collectives, names = (scopes.sync_collectives(text),
                              scopes.op_names(text))
        del text
    del compiled
    used = sys_.devices
    log(f"memory ({time.perf_counter() - t_mem:.1f}s): {mem}")
    del state
    sys_.release()
    sys_ = None
    gc.collect()

    ex = flops.per_example(cell.conf, cell.traffic)
    workers, b_loc = cell.traffic["workers"], cell.traffic["batch_per_worker"]
    tokens = win["steps"] * workers * b_loc * ex["tokens"]
    rate = tokens / win["seconds"]
    log(f"window {win['seconds']:.3f}s {win['rounds']} rounds "
        f"{win['steps']} steps H={h0}: {rate:.1f} tokens/s; peak "
        f"{peak} B; set-up {setup_s:.3f}s (init {setup_init_s:.3f}s)")

    result = {"correct": None, "attempted": win["steps"],
              "failed": win["nonfinite_steps"], "metrics": {},
              "device": {"platform": used[0].platform,
                         "kind": used[0].device_kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": peak}}
    record = {"tokens_per_s": rate, "peak_hbm_gib": peak / GIB,
              "setup_s": setup_s, "setup_init_s": setup_init_s,
              "compiles_in_window": compiles, "chips": cell.chips,
              "flops_per_token": ex["flops"] / ex["tokens"],
              "peak_flops": None, "trace": None,
              # the engine's numeric compile stats and the last round's
              # scalars
              "counters": {**{k: float(v) for k, v in stats.items()
                              if isinstance(v, (int, float))},
                           **win["counters"]}}
    if traced:
        events = trace.compact(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        summ = {**trace.summarize(events, collectives),
                **scopes.summarize(events, names)}
        record["trace"] = summ
        record["peak_flops"] = peaks.peaks(used[0].device_kind)["bf16_flops"]
        busy = [summ["busy_s"][str(d.id)] for d in used
                if str(d.id) in summ["busy_s"]] or list(
                    summ["busy_s"].values())
        result["device"]["busy_s"] = sum(busy) / len(busy)
        result["device"]["window_s"] = summ["window_s"]
        result["breakdown"] = {"device_ops": summ["device_ops"],
                               "idle_gaps": summ["idle_gaps"]}
    result["metrics"] = read_metrics(cell, record, traced)

    ref = reference_readings(cell, seed, reference(cell, used))
    found = compare.gaps(prog, ref)
    limits = cell.limits
    result["correct"] = compare.judge(found, limits)
    log(f"losses program {prog['losses']} reference {ref['losses']}")
    result["compared"] = {k: {"value": v, "limit": limits[k], "at": where}
                          for k, (v, where) in found.items()}
    return result

