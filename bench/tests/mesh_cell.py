#!/usr/bin/env python3
"""The harness on a mesh of four devices, at a small size, in a process of
its own: test_mesh.py runs it with four host devices,

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python3 bench/tests/mesh_cell.py <empty directory>

It writes a benchmark holding the small mesh cell into the directory and
prints, as its last line, one JSON object: the devices that hold each
worker's state, the compiled round's collectives and the part of the round
each belongs to, those the sync metrics read, the numbers compared in a
run of the cell, in runs with each planted fault of test_faults.py, and
for the control (the reference in bfloat16 against the reference, on three
seeds).
"""
from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "tiny-decoder.h2.dp4"
SEED = 2 ** 33 + 7


def _run(root):
    from bench import harness
    out = harness.run_cell(root, CELL, SEED, 0.2, False,
                           t_process=time.perf_counter(), require_tpu=False)
    return {"correct": out["correct"], "compared": out["compared"],
            "attempted": out["attempted"], "count": out["device"]["count"]}


def main(root: str) -> int:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    import pytest

    from bench import compare, harness, manifest, scopes, trace
    from bench.reference import common as C
    from bench.system import System
    from bench.tests.conftest import MESH_CELLS, write_benchmark
    from bench.tests.test_faults import plant

    write_benchmark(root, MESH_CELLS)
    cell = manifest.load_cell(root, CELL)
    out = {"devices": len(jax.devices())}

    sys_ = System(cell)
    state = sys_.init(SEED)
    out["workers_on"] = {
        b: sorted([list(sh.index[0].indices(x.shape[0])), sh.device.id]
                  for sh in x.addressable_shards)
        for b, x in state["params"].items()}
    state, t, _ = harness.first_rounds(sys_, state, SEED)
    text = harness.compiled_round(sys_, state, t).as_text()
    parts = scopes.op_parts(text)
    out["collectives"] = {op: parts.get(op, "other")
                          for op in sorted(trace.collective_ops(text))}
    out["sync_collectives"] = sorted(scopes.sync_collectives(text))
    del state, sys_

    out["run"] = _run(root)
    for fault in ("unchanged", "half_batch", "no_sync"):
        mp = pytest.MonkeyPatch()
        plant(mp, fault)
        try:
            out[fault] = _run(root)
        finally:
            mp.undo()

    devices = jax.devices()[:cell.chips]
    out["control"] = {}
    for seed in (1, 2, 3):
        want = harness.reference_readings(
            cell, seed, harness.reference(cell, devices))
        got = harness.reference_readings(
            cell, seed, harness.reference(cell, devices, num=C.BFLOAT16))
        found = compare.gaps(got, want)
        out["control"][seed] = {"found": found,
                                "passes": compare.judge(found, cell.limits)}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
