#!/usr/bin/env python3
"""Record a small chip trace for the tests in bench/tests/data.

    python3 bench/tests/record_trace.py --workload <cell> --seed <n> \
        --seconds <s> --rounds 3 --out bench/tests/data/<cell>.events.json

Makes one `--trace 1` run of the cell on the chip, prints its result line
as bench/run.py does, and writes the compact event list of the traced
window (`trace.compact`), cut to its first `--rounds` round programs
(`trace.cut`).  Where the round program exchanges data between chips, the
list also holds `collectives`, the instructions `trace.collective_ops`
read from the compiled round, and `parts`, the part of the round each of
them belongs to by the program's scopes (`scopes.op_parts`); the collective
time of each part is printed to standard error.
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
    from bench import harness, scopes, trace

    kept = {}
    compact, collective_ops = trace.compact, trace.collective_ops

    def keep(profile_dir):
        kept["events"] = compact(profile_dir)
        return kept["events"]

    def keep_ops(hlo_text):
        kept["parts"] = scopes.op_parts(hlo_text)
        kept["collectives"] = collective_ops(hlo_text)
        return kept["collectives"]

    trace.compact, trace.collective_ops = keep, keep_ops
    harness.check_chips(1)
    harness.enable_compile_cache()
    result = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                              True, t_process=T_PROCESS)
    events = trace.cut(kept["events"], args.rounds)
    if kept["collectives"]:
        events["collectives"] = sorted(kept["collectives"])
        events["parts"] = {op: kept["parts"].get(op, "other")
                           for op in events["collectives"]}
        by_part = {}
        for op, t in scopes.round_ops(events)[0].items():
            if op in kept["collectives"]:
                part = events["parts"][op]
                by_part[part] = by_part.get(part, 0.0) + t
        harness.log(f"collective self time by part over {args.rounds} "
                    f"rounds, first device: {by_part}")
    with open(args.out, "w") as f:
        json.dump(events, f)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
