"""CLI: `python -m foldprog check` (exit 1 on violations).

Subcommands:

  check   analyze every registered program spec, enforce budgets and
          compare against the golden fingerprints (the CI gate)
  write   re-baseline: analyze and overwrite the golden fingerprints
          (prefer `python scripts/update_fingerprints.py`, which wraps
          this with the right paths)
  list    print the registered program specs and exit (no compilation)

Also runnable as `python tools/foldprog ...` — the bootstrap below puts
tools/ (for the package) and src/ (for repro) on sys.path, and pins the
analysis environment (CPU, hence interpreted Pallas) BEFORE jax is
imported so golden fingerprints are host-independent.
"""
from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parent.parent.parent
if __package__ in (None, ""):                      # python tools/foldprog
    sys.path.insert(0, str(_ROOT / "tools"))

# pin the lowering environment before any jax import: fingerprints must not
# depend on which accelerator the developer's machine happens to have
os.environ.setdefault("JAX_PLATFORMS", "cpu")

try:
    import repro  # noqa: F401  (src/ already on the caller's PYTHONPATH?)
except ImportError:
    sys.path.insert(0, str(_ROOT / "src"))

from foldprog import (REBASELINE, render_report, run_gate,  # noqa: E402
                      write_fingerprints)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="foldprog",
        description="Compile-time program-fingerprint gate for the FOLD "
                    "repro's hot-path JAX programs (trace/lower/compile, "
                    "never execute).")
    ap.add_argument("command", nargs="?", default="check",
                    choices=("check", "write", "list"))
    ap.add_argument("--select", default=None,
                    help="comma-separated program names, name prefixes "
                         "(e.g. 'hnsw') or families to analyze "
                         "(default: all; disables the orphan-golden sweep)")
    ap.add_argument("--fingerprints", default=None,
                    help="golden fingerprint directory override "
                         "(default: tools/foldprog/fingerprints)")
    ap.add_argument("--no-golden", action="store_true",
                    help="budget checks only — skip the F162 drift compare")
    args = ap.parse_args(argv)

    select = ([s.strip() for s in args.select.split(",") if s.strip()]
              if args.select else None)

    if args.command == "list":
        from repro.analysis import default_specs
        for spec in default_specs(select):
            fam = f"  family={spec.family}" if spec.family else ""
            print(f"{spec.name}  donate={spec.donate_expect}{fam}")
        return 0

    reports, violations = run_gate(
        select=select, golden_dir=args.fingerprints,
        golden=(args.command == "check" and not args.no_golden))

    if args.command == "write":
        if violations:
            print(render_report(reports, violations), file=sys.stderr)
            print(f"\nfoldprog: refusing to write goldens while budget "
                  f"checks fail — fix the programs (or their budgets) "
                  f"first", file=sys.stderr)
            return 1
        for p in write_fingerprints(reports, args.fingerprints):
            print(f"wrote {p}")
        return 0

    print(render_report(reports, violations),
          file=sys.stderr if violations else sys.stdout)
    if violations:
        print(f"\nfoldprog: {len(violations)} violation(s); re-baseline "
              f"with `{REBASELINE}` only if the drift is intended",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
