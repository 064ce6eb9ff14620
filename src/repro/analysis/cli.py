"""CLI for the three analysis passes: ``python -m repro.analysis``.

Exit code 0 when no enforced findings remain, 1 otherwise (warnings
count under ``--strict``).  Pass order is cheapest-first so lint
feedback lands before any jax tracing starts.
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path
from typing import List, Optional, Sequence

from repro.analysis import findings as F

PASSES = ("lint", "kernels", "trace")

_RULES = {
    "lint": ("lint-pallas-call", "lint-kernel-import",
             "lint-interpret-kwarg", "lint-wrapper-interpret",
             "lint-registry-complete", "lint-parse"),
    "kernels": ("kernel-signature", "kernel-example", "kernel-trace",
                "kernel-block-div", "kernel-tpu-tiling", "kernel-grid",
                "kernel-vmem"),
    "trace": ("trace-weight-quant", "trace-dequant", "trace-f64",
              "trace-host-transfer", "trace-stage-coverage",
              "trace-mesh-bake", "trace-retrace"),
}


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="Static analysis: serving-trace invariants, Pallas "
                    "kernel validation, and repo lint (docs/analysis.md).")
    ap.add_argument("--strict", action="store_true",
                    help="treat warnings as errors")
    ap.add_argument("--passes", default=",".join(PASSES),
                    help=f"comma-separated subset of {PASSES}")
    ap.add_argument("--disable", action="append", default=[],
                    metavar="RULE", help="skip a rule (repeatable); "
                    "see --list-rules")
    ap.add_argument("--root", default=".",
                    help="repo root for the lint pass (default: cwd)")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule catalog and exit")
    return ap


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = _parser().parse_args(argv)
    if args.list_rules:
        for pass_name, rules in _RULES.items():
            print(f"{pass_name}:")
            for r in rules:
                print(f"  {r}")
        return 0

    selected = [p.strip() for p in args.passes.split(",") if p.strip()]
    unknown = sorted(set(selected) - set(PASSES))
    if unknown:
        print(f"unknown pass(es) {unknown}; choose from {list(PASSES)}",
              file=sys.stderr)
        return 2
    disable = tuple(args.disable)

    all_findings: List[F.Finding] = []
    for pass_name in PASSES:
        if pass_name not in selected:
            continue
        t0 = time.monotonic()
        if pass_name == "lint":
            from repro.analysis import repolint
            fs = repolint.run(Path(args.root), disable=disable)
        elif pass_name == "kernels":
            from repro.analysis import kernel_checks
            fs = kernel_checks.run(disable=disable)
        else:
            from repro.analysis import trace_invariants
            mesh = trace_invariants.default_mesh()
            fs = trace_invariants.run(mesh=mesh, disable=disable)
        dt = time.monotonic() - t0
        status = "ok" if not fs else f"{len(fs)} finding(s)"
        print(f"[{pass_name}] {status} ({dt:.1f}s)")
        if fs:
            print(F.render(fs))
        all_findings += fs

    enforced = F.errors(all_findings, strict=args.strict)
    if enforced:
        print(f"\nFAIL: {len(enforced)} enforced finding(s)")
        return 1
    print("\nOK: all analysis passes clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
