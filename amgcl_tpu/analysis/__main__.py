"""``python -m amgcl_tpu.analysis`` — run the linter and the jaxpr
auditor against the committed findings budget (ANALYSIS_BASELINE.json).

Exit status 0 when there are no NEW lint findings (anything not in the
baseline's suppression list) and no audit contract errors; 1 otherwise
— the same gate shape as ``bench.py --gate``. ``bench.py --check`` runs
this module and embeds the record.

The auditor needs a multi-device mesh for the collective census; when
jax has not been imported yet this module forces the test topology
(CPU backend, 8 virtual devices) exactly like tests/conftest.py, so the
audit sees the same programs CI tests.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _force_test_topology() -> None:
    """CPU backend, 8 virtual devices, x64 on — the tests/conftest.py
    topology, FORCED unconditionally: the audit is static (nothing
    executes), so the accelerator an ambient ``JAX_PLATFORMS`` points at
    is irrelevant, while the collective census silently degrades to a
    skip without the virtual mesh. jax reads XLA_FLAGS lazily at BACKEND
    initialization, so this works even though importing amgcl_tpu (which
    ``python -m`` does before this module runs) already imported jax —
    as long as no computation has happened yet, which is the case at
    CLI startup."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    # jax read JAX_PLATFORMS when it was imported: set the config too
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m amgcl_tpu.analysis", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--json", action="store_true",
                    help="emit the full record as one JSON object")
    ap.add_argument("--baseline", metavar="PATH",
                    help="findings-budget file (default: the committed "
                         "ANALYSIS_BASELINE.json)")
    ap.add_argument("--no-audit", action="store_true",
                    help="lint + concurrency only (no jax import; fast "
                         "enough for a pre-commit hook)")
    ap.add_argument("--no-concurrency", action="store_true",
                    help="skip the concurrency contract analyzer "
                         "(analysis/concurrency.py; default ON)")
    ap.add_argument("--root", metavar="DIR",
                    help="package root to analyze instead of the "
                         "installed amgcl_tpu/ (negative-injection "
                         "fixtures and forks)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="rewrite the baseline accepting every current "
                         "finding (reasons are kept for keys already "
                         "suppressed; new entries get a TODO reason to "
                         "fill in before committing)")
    args = ap.parse_args(argv)

    from amgcl_tpu import analysis

    baseline_path = args.baseline or analysis.BASELINE_PATH
    baseline = analysis.load_baseline(baseline_path)

    if args.write_baseline:
        findings = analysis.run_lint(root=args.root)
        if not args.no_concurrency:
            findings = findings + analysis.run_concurrency(
                root=args.root)
        old = {(s["rule"], s["file"], s["symbol"]): s.get("reason", "")
               for s in (baseline or {}).get("suppressions", [])}
        seen, sup = set(), []
        for f in findings:
            key = analysis.finding_key(f)
            if key in seen:
                continue
            seen.add(key)
            sup.append({"rule": key[0], "file": key[1], "symbol": key[2],
                        "reason": old.get(key,
                                          "TODO: justify or fix")})
        if args.no_concurrency:
            # a lint-only rewrite ran no concurrency rules: keep the
            # existing concurrency budget verbatim instead of silently
            # dropping it (the default run would then fail on 'new'
            # findings the analyzer had already accepted)
            for s in (baseline or {}).get("suppressions", []):
                if s.get("rule") in analysis.CONCURRENCY_RULES \
                        and analysis.finding_key(s) not in seen:
                    sup.append(s)
        with open(baseline_path, "w") as fh:
            json.dump({"version": 1, "suppressions": sup}, fh, indent=1)
            fh.write("\n")
        print("wrote %d suppression(s) to %s"
              % (len(sup), baseline_path))
        return 0

    if not args.no_audit:
        _force_test_topology()
    rec = analysis.run_all(baseline=baseline,
                           with_audit=not args.no_audit,
                           with_concurrency=not args.no_concurrency,
                           root=args.root)
    if args.json:
        print(json.dumps(rec, default=str))
    else:
        lint_rec = rec["lint"]
        print("Lint: %d finding(s), %d suppressed by baseline, %d new"
              % (lint_rec["total"], lint_rec["suppressed"],
                 len(lint_rec["new"])))
        if lint_rec["new"]:
            print(analysis.format_findings(lint_rec["new"]))
        if "concurrency" in rec:
            conc = rec["concurrency"]
            print("Concurrency: %d finding(s) over %d declared "
                  "module(s), %d suppressed by baseline, %d new"
                  % (conc["total"], len(conc["modules"]),
                     conc["suppressed"], len(conc["new"])))
            if conc["new"]:
                print(analysis.format_findings(conc["new"]))
        for s in lint_rec["stale_suppressions"]:
            print("stale suppression (finding gone — remove from "
                  "baseline): %s %s %s" % (s["rule"], s["file"],
                                           s["symbol"]))
        if "audit" in rec:
            from amgcl_tpu.analysis import jaxpr_audit
            print()
            print(jaxpr_audit.format_report(rec["audit"]))
        print()
        print("ANALYSIS %s" % ("OK" if rec["ok"] else "FAIL"))
    return 0 if rec["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
