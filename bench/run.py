#!/usr/bin/env python3
"""The benchmark: one run of one cell of BENCHMARK.json.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints the device and kernel backend on earlier lines, then as its last
line of standard output one JSON object: correct, attempted, failed,
metrics (the cell's end-to-end metrics, or with --trace 1 its per-layer
metrics), device and, with --trace 1, breakdown; the numbers compared for
`correct` come last there and as the last lines of standard error.  Exits
non-zero with no result line without a TPU, with fewer chips than the cell
asks for, or without the program beside the benchmark.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    try:
        import repro  # noqa: F401  (the program under test)
    except ImportError as e:
        print(f"bench: the program is not beside the benchmark: {e}",
              file=sys.stderr)
        return 2
    from bench import harness

    try:
        harness.check_chips(1)
        harness.log(f"compile cache {harness.enable_compile_cache()}")
        result = harness.run_cell(ROOT, args.workload, args.seed,
                                  args.seconds, bool(args.trace),
                                  t_process=T_PROCESS)
    except harness.ChipError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for name, c in result["compared"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r} at {c['at']}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
