#!/usr/bin/env python3
"""Record a chip trace split by the round's scopes, for the tests in
bench/tests/data.

    python3 bench/tests/record_scopes.py --workload <cell> --seed <n> \
        --seconds <s> --rounds 3 --out bench/tests/data/<cell>.scopes.json \
        [--hlo <file>.hlo.txt.gz]

Makes one `--trace 1` run of the cell on the chip and prints its result
line as bench/run.py does.  Writes the compact event list of the traced
window with the engine's host spans (`scopes.engine_spans`), cut to its
first `--rounds` round programs (`trace.cut`), beside the `op_name` of
each op in the cut (`scopes.op_names` of the compiled round the window
ran) and the harness's split of the whole window (its record's
`trace["parts"]` and `trace["scopes"]`).  Then prints one JSON line: that
split, the device-idle time under each engine span per round (both in
ms), the longest ops of other, and the engine's compile seconds.  `--hlo`
also keeps the compiled round's text, to look up by hand what an op of
other is.
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import sys
import time

T_PROCESS = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TOP = 10


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--out", required=True)
    ap.add_argument("--hlo", help="also write the compiled round's text "
                    "here, gzipped")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness, scopes, trace
    from repro.core.engine import RoundEngine

    kept = {}
    compact, compiled_round = trace.compact, RoundEngine.compiled_round
    read_metrics = harness.read_metrics

    def keep_events(profile_dir):
        events = compact(profile_dir)
        kept["events"] = scopes.engine_spans(events, profile_dir)
        return events

    def keep_text(self, *a, **k):
        compiled = compiled_round(self, *a, **k)
        kept["text"] = compiled.as_text()
        return compiled

    def keep_record(cell, record, traced):
        kept["record"] = record
        return read_metrics(cell, record, traced)

    trace.compact = keep_events
    RoundEngine.compiled_round = keep_text
    harness.read_metrics = keep_record
    harness.check_chips(1)
    harness.enable_compile_cache()
    result = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                              True, t_process=T_PROCESS)
    print(json.dumps(result), flush=True)

    if args.hlo:
        with gzip.open(args.hlo, "wt") as f:
            f.write(kept["text"])
    events, names = kept["events"], scopes.op_names(kept["text"])
    summ, counters = kept["record"]["trace"], kept["record"]["counters"]
    split = {k: summ[k] for k in ("rounds", "round_busy_s", "parts",
                                  "scopes")}
    cut = trace.cut(events, args.rounds)
    ops = {scopes.instruction(cut["names"][e[0]])
           for dev in cut["devices"].values() for e in dev["ops"]}
    with open(args.out, "w") as f:
        json.dump({"cell": args.workload, "seed": args.seed,
                   "op_names": {op: names[op] for op in sorted(ops)
                                if op in names},
                   "trace": split, "events": cut}, f)

    by_op, rounds = scopes.round_ops(events)
    full = {scopes.instruction(n): n[:trace.NAME_CHARS]
            for n in events["names"]}
    other = sorted(((t / rounds * 1e3, full[op]) for op, t in by_op.items()
                    if scopes.part_of(names.get(op, "")) == "other"),
                   reverse=True)
    print(json.dumps({
        "cell": args.workload, "rounds": rounds,
        "round_device_ms": summ["round_busy_s"] * 1e3,
        "host_gap_ms": summ["round_gap_idle_s"] * 1e3,
        "split_ms": {k: v * 1e3 for k, v in summ["parts"].items()},
        "engine_idle_ms": {k: v * 1e3 for k, v in
                           scopes.engine_idle(events).items()},
        "other_ops_ms": other[:TOP],
        "compile_s": counters["compile_s"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
