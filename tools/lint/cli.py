"""CLI + CI gate: `python -m tools.lint --gate`.

The gate follows the bench_history `--gate` pattern: findings diff
against the committed LINT_BASELINE.json — a pre-existing accepted
finding is identified by its stable fingerprint and carries a written
justification; any finding with NO baseline row is NEW and fails the
gate (exit 1). A baseline row whose fingerprint no longer matches
anything in the tree is STALE (reported on stderr, exit unchanged —
prune it in the same PR that fixed the finding). A baseline row with
an empty justification does NOT suppress: accepting a finding means
writing down why.

    python -m tools.lint                      # report everything
    python -m tools.lint --gate               # CI: fail on new findings
    python -m tools.lint --only lockcheck,blocking
    python -m tools.lint --only contracts     # determinism sweep only
    python -m tools.lint --format dot         # lock graph for graphviz
    python -m tools.lint --write-baseline     # (re)seed the baseline —
                                              # justifications stay ""
                                              # until a human writes
                                              # them; warns when a
                                              # carried-over row's
                                              # recorded severity no
                                              # longer matches the live
                                              # finding (drift)
    python -m tools.lint --write-wiremsg-schema   # record a wire-
                                              # schema evolution
    python -m tools.lint --report split       # ARM the runtime
                                              # sanitizer, soak, print
                                              # the process-split
                                              # feasibility report
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Optional

from . import blocking, conventions, jaxhazard, lockcheck, wiremsg
from .facts import RepoFacts, extract_repo
from .findings import Finding, sort_findings

PASSES = (
    "lockcheck", "blocking", "jaxhazard", "metrics", "spans",
    "lifecycle", "contracts", "wiremsg",
)

# rule-name prefix per pass: lets a --only run judge staleness (and
# baseline merging) ONLY for rows its selected passes could have
# re-found — other passes' live rows must not be called stale
_RULE_PREFIX = {
    "lockcheck": "lock-",
    "blocking": "blocking-",
    "jaxhazard": "jax-",
    "metrics": "metric-",
    "spans": "span-",
    "lifecycle": "lifecycle-",
    "contracts": "contract-",
    "wiremsg": "wiremsg-",
}

DEFAULT_BASELINE = "LINT_BASELINE.json"


def _row_in_passes(row: dict, selected: tuple) -> bool:
    rule = str(row.get("rule", ""))
    return any(rule.startswith(_RULE_PREFIX[p]) for p in selected)


def run_passes(
    root: str,
    only: Optional[tuple[str, ...]] = None,
    subdirs: tuple[str, ...] = ("corda_tpu",),
) -> tuple[RepoFacts, list[Finding]]:
    repo = extract_repo(root, subdirs)
    selected = tuple(only) if only else PASSES
    findings: list[Finding] = []
    if "lockcheck" in selected:
        findings += lockcheck.run(repo)
    if "blocking" in selected:
        findings += blocking.run(repo)
    if "jaxhazard" in selected:
        findings += jaxhazard.run(repo)
    if "metrics" in selected:
        findings += conventions.run_metrics(repo)
    if "spans" in selected:
        findings += conventions.run_spans(repo)
    if "lifecycle" in selected:
        findings += conventions.run_lifecycle(repo)
    if "contracts" in selected:
        findings += conventions.run_contracts(repo)
    if "wiremsg" in selected:
        findings += wiremsg.run(repo)
    return repo, sort_findings(findings)


def load_baseline(path: str) -> list[dict]:
    if not os.path.exists(path):
        return []
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    rows = doc.get("baselined", []) if isinstance(doc, dict) else []
    return [r for r in rows if isinstance(r, dict)]


def write_baseline(
    path: str,
    findings: list[Finding],
    selected: tuple = PASSES,
) -> list[str]:
    """(Re)seed the baseline from the current findings, MERGING with
    what is already committed: an existing row's hand-written
    justification is preserved when its finding still fires, and rows
    belonging to passes that were not run (--only) are kept verbatim —
    re-seeding must never erase accepted history. Rows for a selected
    pass whose finding no longer fires are dropped (they would only go
    stale). New findings get an empty justification for a human to
    fill in.

    Returns justification-DRIFT warnings: a carried-over justification
    was written against the finding as it then stood — when the live
    finding's severity no longer matches what the row recorded, the
    prose may argue about a finding that no longer exists in that
    form, so the human is told to re-verify it."""
    existing = {r.get("fingerprint"): r for r in load_baseline(path)}
    rows = []
    seen = set()
    drift: list[str] = []
    for f in findings:
        if f.fingerprint in seen:
            continue
        seen.add(f.fingerprint)
        prior = existing.get(f.fingerprint, {})
        justification = str(prior.get("justification", ""))
        if (
            justification.strip()
            and str(prior.get("severity", f.severity)) != f.severity
        ):
            # byte-identical twin in corda_tpu/testing/sanitizer.py's
            # write_baseline — the static and dynamic planes share one
            # baseline discipline; change both or neither
            drift.append(
                f"baseline row {f.fingerprint} ({f.rule} {f.file}): "
                f"recorded severity {prior.get('severity')} but the "
                f"live finding is {f.severity} — the carried-over "
                "justification may no longer apply, re-verify it"
            )
        rows.append(
            {
                "fingerprint": f.fingerprint,
                "rule": f.rule,
                "severity": f.severity,
                "file": f.file,
                "scope": f.scope,
                "detail": f.detail,
                "justification": justification,
            }
        )
    for fp, row in existing.items():
        if fp not in seen and not _row_in_passes(row, selected):
            rows.append(row)
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"version": 1, "baselined": rows}, f, indent=2)
        f.write("\n")
    return drift


def gate(
    findings: list[Finding],
    baseline_rows: list[dict],
    selected: tuple = PASSES,
) -> tuple[list[Finding], list[dict], list[dict]]:
    """(new findings, stale rows, unjustified rows). Staleness is
    judged only for rows belonging to `selected` passes: a --only run
    cannot re-find the other passes' findings, so their live rows must
    not be reported as prunable."""
    justified = {
        r["fingerprint"]
        for r in baseline_rows
        if r.get("fingerprint") and str(r.get("justification", "")).strip()
    }
    unjustified = [
        r
        for r in baseline_rows
        if r.get("fingerprint")
        and not str(r.get("justification", "")).strip()
        and _row_in_passes(r, selected)
    ]
    live = {f.fingerprint for f in findings}
    new = [f for f in findings if f.fingerprint not in justified]
    stale = [
        r
        for r in baseline_rows
        if r.get("fingerprint")
        and r["fingerprint"] not in live
        and _row_in_passes(r, selected)
    ]
    return new, stale, unjustified


def main(argv: Optional[list[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m tools.lint",
        description="concurrency & JAX-hazard static analyzer",
    )
    p.add_argument(
        "--root",
        default=os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        ),
        help="repo root (default: the checkout containing tools/)",
    )
    p.add_argument(
        "--paths",
        default="corda_tpu",
        help="comma-separated scan roots relative to --root",
    )
    p.add_argument(
        "--only",
        default=None,
        help=f"comma-separated pass subset from: {', '.join(PASSES)}",
    )
    p.add_argument(
        "--gate",
        action="store_true",
        help="CI mode: exit 1 on any finding absent from the baseline",
    )
    p.add_argument(
        "--baseline",
        default=None,
        help=f"baseline path (default: <root>/{DEFAULT_BASELINE})",
    )
    p.add_argument(
        "--write-baseline",
        action="store_true",
        help="write the current findings as the baseline (empty "
        "justifications — fill them in before committing)",
    )
    p.add_argument(
        "--format",
        choices=("text", "json", "dot"),
        default="text",
        help="dot prints the lock-acquisition graph instead of findings",
    )
    p.add_argument(
        "--write-wiremsg-schema",
        action="store_true",
        help="(re)generate WIREMSG_SCHEMA.json from the scanned tree "
        "— the explicit act that records a wire-schema evolution",
    )
    p.add_argument(
        "--report",
        choices=("split",),
        default=None,
        help="'split' arms the runtime sanitizer, drives the standard "
        "soak and prints the process-split feasibility report "
        "(static sharing map x measured contention/hold times); "
        "imports corda_tpu, unlike every other mode",
    )
    args = p.parse_args(argv)

    only = None
    if args.only:
        only = tuple(s.strip() for s in args.only.split(",") if s.strip())
        unknown = [s for s in only if s not in PASSES]
        if unknown:
            print(
                f"lint: unknown pass(es): {', '.join(unknown)} "
                f"(have: {', '.join(PASSES)})",
                file=sys.stderr,
            )
            return 2
    subdirs = tuple(
        s.strip() for s in args.paths.split(",") if s.strip()
    )

    if args.report == "split":
        return _report_split(args.root)

    if args.write_wiremsg_schema:
        repo = extract_repo(args.root, subdirs)
        path = wiremsg.write_schema(args.root, repo)
        print(
            f"lint: wrote {len(wiremsg.scoped_messages(repo))} wire "
            f"message shape(s) to {path}"
        )
        return 0

    t0 = time.perf_counter()
    repo, findings = run_passes(args.root, only, subdirs)
    elapsed = time.perf_counter() - t0

    if args.format == "dot":
        print(lockcheck.to_dot(repo))
        return 0

    baseline_path = args.baseline or os.path.join(
        args.root, DEFAULT_BASELINE
    )
    if args.write_baseline:
        drift = write_baseline(baseline_path, findings, only or PASSES)
        for warning in drift:
            print(f"lint: DRIFT {warning}", file=sys.stderr)
        print(
            f"lint: wrote {len(findings)} finding(s) to {baseline_path} "
            "— add justifications before committing"
        )
        return 0

    if args.format == "json":
        print(
            json.dumps(
                [
                    {
                        "fingerprint": f.fingerprint,
                        "pass": f.pass_name,
                        "rule": f.rule,
                        "severity": f.severity,
                        "file": f.file,
                        "line": f.line,
                        "scope": f.scope,
                        "detail": f.detail,
                        "message": f.message,
                    }
                    for f in findings
                ],
                indent=2,
            )
        )

    if not args.gate:
        if args.format == "text":
            for f in findings:
                print(f.render())
            print(
                f"lint: {len(findings)} finding(s) over "
                f"{len(repo.modules)} modules in {elapsed:.2f}s"
            )
        return 0

    # -- gate mode -----------------------------------------------------------
    rows = load_baseline(baseline_path)
    new, stale, unjustified = gate(findings, rows, only or PASSES)
    for r in unjustified:
        print(
            f"lint: baseline row {r['fingerprint']} ({r.get('rule')}) "
            "has no justification — it does not suppress",
            file=sys.stderr,
        )
    for r in stale:
        print(
            f"lint: STALE baseline row {r['fingerprint']} "
            f"({r.get('rule')} {r.get('file')}): no longer found — "
            "prune it",
            file=sys.stderr,
        )
    if new:
        print(
            f"lint: GATE {len(new)} new finding(s) not in "
            f"{os.path.basename(baseline_path)}:",
            file=sys.stderr,
        )
        if args.format == "text":
            for f in new:
                print(f.render())
        return 1
    if args.format == "text":
        print(
            f"lint: gate clean — {len(findings)} finding(s), all "
            f"baselined with justification "
            f"({len(repo.modules)} modules, {elapsed:.2f}s)"
        )
    return 0


def _report_split(root: str) -> int:
    """`--report split`: the runtime half. Arms the sanitizer, drives
    the standard soak (sharded batching notary, a submitting client
    thread, durable intake, concurrent readers) and prints the process-split
    feasibility report plus the static<->dynamic reconciliation. The
    one lint mode that imports corda_tpu (lazily — the static gate
    stays dependency-free)."""
    if root not in sys.path:
        sys.path.insert(0, root)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from corda_tpu.testing import sanitizer as szr

    view = szr.static_lock_view(root)
    san = szr.ConcurrencySanitizer(
        hot_locks=view.hot_locks, hold_budget_micros=2_000
    )
    t0 = time.perf_counter()
    with san:
        out = szr.standard_soak()
    elapsed = time.perf_counter() - t0
    diff = san.diff_static(view)
    print(szr.render_split_report(san.split_report(view)))
    print()
    print(
        f"static<->dynamic: {diff.observed_edge_count} observed "
        f"edge(s), {len(diff.unseen_edges)} unseen, "
        f"{len(diff.unexercised_edges)} statically-known never "
        f"exercised (coverage {diff.coverage:.0%}), "
        f"{len(diff.unknown_locks)} unknown runtime lock name(s)"
    )
    for f in diff.unseen_edges:
        print(f.render())
    for f in san.findings():
        print(f.render())
    print(
        f"lint: split report over a {out['signed']}-signed/"
        f"{out['rejected']}-rejected soak in {elapsed:.2f}s"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
