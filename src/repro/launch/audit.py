import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")  # simulated host devices
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_EXTRA_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=" +
                           os.environ.get("REPRO_DRYRUN_DEVICES", "8")).strip()
# ^ MUST run before any other import: jax locks the device count on first init.

"""Static program auditor CLI (README §Static audit).

Evaluates the declarative rule registry (repro.analysis.rules) against
the AOT-lowered HLO of every supported configuration — sync sub-programs
per (layout x wire x mesh), full round programs with donated state, and
the statically-enumerated compile-cache key space — plus the AST source
lint over src/repro/.  Nothing executes: every verdict lands at lower
time, before any collective runs.

  PYTHONPATH=src python -m repro.launch.audit --all --diff-baseline
  PYTHONPATH=src python -m repro.launch.audit --all --update-baseline
  PYTHONPATH=src python -m repro.launch.audit --config KEY [--config KEY]
  PYTHONPATH=src python -m repro.launch.audit --list | --rules
  PYTHONPATH=src python -m repro.launch.audit --lint
  PYTHONPATH=src python -m repro.launch.audit --self-test

Exit status is non-zero on any rule violation, baseline regression, lint
finding, or uncaught mutation — the CI `static` job gates on it.
"""
import argparse
import json
import sys


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--all", action="store_true",
                    help="audit the full config matrix")
    ap.add_argument("--config", action="append", default=[],
                    help="audit only this matrix key (repeatable)")
    ap.add_argument("--list", action="store_true",
                    help="print the matrix keys and exit")
    ap.add_argument("--rules", action="store_true",
                    help="print the registered rules and exit")
    ap.add_argument("--lint", action="store_true",
                    help="run the AST source lint over src/repro/")
    ap.add_argument("--self-test", action="store_true",
                    help="mutation self-test: deliberately broken programs "
                         "must each trip their rule")
    ap.add_argument("--diff-baseline", action="store_true",
                    help="fail on any regression vs the committed baseline")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the committed baseline from this audit")
    ap.add_argument("--baseline", default=None,
                    help="baseline path (default: the committed "
                         "analysis/audit_baseline.json)")
    ap.add_argument("--out", default=None,
                    help="also write the fingerprint JSON to this path "
                         "(the CI static job uploads it as an artifact)")
    args = ap.parse_args()

    from repro.analysis import audit as A
    from repro.analysis import rules as R
    from repro.analysis import source_lint as L

    status = 0

    if args.list:
        for key, cfg in sorted(A.matrix().items()):
            print(key)
        return 0
    if args.rules:
        for name, rule in sorted(R.RULES.items()):
            print(f"{name}: {rule.description}")
        return 0

    if args.lint:
        violations = L.lint_repo()
        for v in violations:
            print(v.render())
        print(f"source lint: {len(violations)} violation(s)")
        status |= bool(violations)

    if args.self_test:
        failures = A.self_test()
        for f in failures:
            print(f"SELF-TEST FAILURE: {f}")
        print(f"mutation self-test: {len(failures)} failure(s)")
        status |= bool(failures)

    if args.all or args.config:
        fresh = A.run_audit(args.config or None)
        if args.out:
            with open(args.out, "w", encoding="utf-8") as fh:
                json.dump(fresh, fh, indent=1, sort_keys=True)
        bad = {k: e["rules_failed"] for k, e in fresh["configs"].items()
               if e["rules_failed"]}
        for key, failed_rules in sorted(bad.items()):
            for rule in failed_rules:
                for viol in fresh["configs"][key]["rules"][rule]["violations"]:
                    print(f"RULE VIOLATION {key}: {rule}: {viol}")
        n = len(fresh["configs"])
        print(f"audited {n} config(s): "
              f"{n - len(bad)} clean, {len(bad)} violating")
        status |= bool(bad)

        if args.update_baseline:
            path = args.baseline or A.BASELINE_PATH
            with open(path, "w", encoding="utf-8") as fh:
                json.dump(fresh, fh, indent=1, sort_keys=True)
                fh.write("\n")
            print(f"baseline updated: {path}")
        elif args.diff_baseline:
            baseline = A.load_baseline(args.baseline)
            regressions, notes = A.diff_baseline(fresh, baseline)
            for r in regressions:
                print(f"REGRESSION vs baseline: {r}")
            for nline in notes:
                print(f"note: {nline}")
            print(f"baseline diff: {len(regressions)} regression(s), "
                  f"{len(notes)} note(s)")
            status |= bool(regressions)

    return status


if __name__ == "__main__":
    sys.exit(main())
