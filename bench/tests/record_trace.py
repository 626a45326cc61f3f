#!/usr/bin/env python3
"""Record a small chip trace for the tests in bench/tests/data.

    python3 bench/tests/record_trace.py --workload <cell> --seed <n> \
        --seconds <s> --rounds 3 --out bench/tests/data/<cell>.events.json

Makes one `--trace 1` run of the cell on the chip, prints its result line
as bench/run.py does, and writes the compact event list of the traced
window (`trace.compact`), cut to its first `--rounds` round programs
(`trace.cut`).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_PROCESS = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness, trace

    kept = {}
    compact = trace.compact

    def keep(profile_dir):
        kept["events"] = compact(profile_dir)
        return kept["events"]

    trace.compact = keep
    harness.check_chips(1)
    harness.enable_compile_cache()
    result = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                              True, t_process=T_PROCESS)
    with open(args.out, "w") as f:
        json.dump(trace.cut(kept["events"], args.rounds), f)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
