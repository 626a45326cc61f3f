#!/usr/bin/env python3
"""Readings that set a cell's limits, on the chip, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3 --out readings.json

For every seed of --seeds: the program's first rounds through the timed
path (`harness.first_rounds`, the compiled program of the window) and the
reference's, and the three numbers of `bench/compare.py` between them: the
lower readings.  For every seed of --control-seeds, the same numbers for
what the comparison has to catch, each put in the program's place: the
reference in bfloat16 (the control) and the reference with each planted
fault of `bench/reference/train.py`.  The benchmark's own runs never run
this.  `limits` applies the rule of PERF.md to what it read.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def limits(readings: dict) -> dict:
    """Per number: the lower reading (largest over the program's seeds),
    the upper (smallest over the control's seeds where that is 3x the lower
    or more, and over each fault's where that is 10x or more; a state left
    unchanged reads 1 on change_gap and grad_gap), and a limit two thirds
    of the way from lower to upper on a log scale."""
    out = {}
    for k in ("loss_gap", "grad_gap", "change_gap"):
        lower = max(r[k][0] for r in readings["program"].values())
        cands = []
        ctl = [r[k][0] for r in readings.get("control", {}).values()]
        if ctl and min(ctl) >= 3 * lower:
            cands.append(("control", min(ctl)))
        for f in ("half_batch", "no_sync"):
            got = [r[k][0] for r in readings.get(f, {}).values()]
            if got and min(got) >= 10 * lower:
                cands.append((f, min(got)))
        if k != "loss_gap" and 1.0 >= 3 * lower:
            cands.append(("unchanged", 1.0))
        upper = min(cands, key=lambda c: c[1]) if cands else None
        limit = (lower ** (1 / 3) * upper[1] ** (2 / 3)
                 if upper and lower > 0 else None)
        out[k] = {"lower": lower, "upper": upper, "limit": limit}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import gc

    from bench import compare, harness, manifest
    from bench.reference import common as C
    from bench.system import System

    cell = manifest.load_cell(ROOT, args.workload)
    harness.check_chips(cell.chips)
    harness.enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    ctl_seeds = [int(s) for s in args.control_seeds.split(",") if s]

    prog = {}
    sys_ = System(cell)
    for seed in seeds:
        t = time.perf_counter()
        state = sys_.init(seed)
        state, _, prog[seed] = harness.first_rounds(sys_, state, seed)
        del state
        harness.log(f"program seed {seed}: {time.perf_counter() - t:.1f}s "
                    f"losses {prog[seed]['losses']}")
    devices = sys_.devices
    sys_.release()
    sys_ = None
    gc.collect()

    variants = {"control": dict(num=C.BFLOAT16),
                "half_batch": dict(faults=("half_batch",)),
                "no_sync": dict(faults=("no_sync",))}
    ref = harness.reference(cell, devices)
    others = {k: harness.reference(cell, devices, **kw)
              for k, kw in variants.items()}
    out = {"workload": args.workload, "program": {}}
    for seed in seeds:
        t = time.perf_counter()
        want = harness.reference_readings(cell, seed, ref)
        out["program"][seed] = compare.gaps(prog[seed], want)
        harness.log(f"seed {seed} program {out['program'][seed]} "
                    f"({time.perf_counter() - t:.1f}s)")
        if seed not in ctl_seeds:
            continue
        for k, r in others.items():
            got = harness.reference_readings(cell, seed, r)
            out.setdefault(k, {})[seed] = compare.gaps(got, want)
            harness.log(f"seed {seed} {k} {out[k][seed]}")
    out["limits"] = limits(out)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out["limits"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
