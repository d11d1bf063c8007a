"""Static audit CLI: program invariants + repo lint, with a committed
baseline gate.

    python scripts/audit.py                      # run both passes
    python scripts/audit.py --baseline audit_baseline.json
    python scripts/audit.py --write-baseline     # refresh the pin
    python scripts/audit.py --lint-only          # no jax, instant
    python scripts/audit.py --no-flow            # file-local rules only
    python scripts/audit.py --json report.json   # full report dump

The lint pass runs both tiers by default: the file-local legacy rules
and the flowlint whole-program checkers (call-graph trace-purity,
PRNG-key discipline, wire-dtype crossing, lock-confinement).
``--no-flow`` skips the flow tier (escape hatch for a broken parse —
file-local rules still run).

Exit status: 0 clean, 1 on any invariant failure, unwaived lint hit,
or baseline regression. The program pass always runs on the canonical
8-device virtual CPU mesh (forced below, before jax initialises its
backends) — the audit checks program *shape*, which is
platform-independent, and fingerprints are only stable on one
canonical topology.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# must precede any jax import (tests/conftest.py does the same dance)
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--baseline", default=None,
                    help="diff the report against this JSON baseline")
    ap.add_argument("--write-baseline", metavar="PATH", nargs="?",
                    const="audit_baseline.json", default=None,
                    help="write the pinned baseline (default "
                         "audit_baseline.json) and exit 0 if the "
                         "audit itself is clean")
    ap.add_argument("--json", default=None,
                    help="dump the full report to this path")
    ap.add_argument("--lint-only", action="store_true")
    ap.add_argument("--program-only", action="store_true")
    ap.add_argument("--flow", dest="flow", action="store_true",
                    default=True,
                    help="run the flowlint whole-program checkers "
                         "(default)")
    ap.add_argument("--no-flow", dest="flow", action="store_false",
                    help="skip the flow tier; file-local rules only")
    args = ap.parse_args(argv)

    from commefficient_tpu.analysis import lint as lint_mod
    lint_summary = {"unwaived": [], "waived": [], "stale_waivers": []}
    if not args.program_only:
        if args.flow:
            violations = lint_mod.run_all()
            stale = lint_mod.stale_waivers(violations=violations)
        else:
            violations = lint_mod.run_lint()
            stale = lint_mod.stale_waivers(
                violations=violations,
                rule_names=[r.name for r in lint_mod.LEGACY_RULES])
        lint_summary = lint_mod.lint_report(violations, stale=stale)
        for v in lint_summary["unwaived"]:
            print(f"LINT  {v}")
        for v in stale:
            print(f"STALE {v}")
        print(f"lint: {len(lint_summary['unwaived'])} unwaived, "
              f"{len(lint_summary['waived'])} waived, "
              f"{len(stale)} stale waiver(s)")

    program_report = {"programs": {}, "failures": []}
    if not args.lint_only:
        from commefficient_tpu.analysis.program import \
            run_program_audit
        program_report = run_program_audit()
        for name, entry in program_report["programs"].items():
            status = "FAIL" if entry["failures"] else "ok"
            cols = entry.get("collectives", {}).get("counts", {})
            print(f"{status:4}  {name:28} "
                  f"fp {entry['fingerprint'][:12]}  "
                  f"collectives {cols or '{}'}")
        for msg in program_report["failures"]:
            print(f"AUDIT {msg}")

    from commefficient_tpu.analysis import baseline as base_mod
    report = base_mod.build_report(program_report, lint_summary)

    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
        print(f"report -> {args.json}")

    failures = list(report["failures"])
    if args.write_baseline:
        if failures:
            print(f"\nNOT writing baseline: {len(failures)} hard "
                  "failure(s) — fix or waive them first")
        else:
            base_mod.save_baseline(report, args.write_baseline)
            print(f"baseline -> {args.write_baseline}")
    elif args.baseline:
        problems = base_mod.diff_against_baseline(
            report, base_mod.load_baseline(args.baseline))
        # diff_against_baseline folds the hard failures in
        failures = problems
        for p in problems:
            print(f"DIFF  {p}")

    if failures:
        print(f"\naudit: {len(failures)} failure(s)")
        return 1
    print("\naudit: clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
