"""Benchmark suite: one section per paper table/figure.

Usage:  PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]
        PYTHONPATH=src python -m benchmarks.run --backend KEY [--quick]

Prints `name,us_per_call,derived` CSV rows per the harness contract, where
us_per_call is the per-document processing latency of the subject system
and `derived` carries the figure's headline metric (recall, speedup, ...).
Each benchmark additionally lands a machine-readable `BENCH_<name>.json`
at the repo root for trend tracking across commits.

--backend runs the generic continuous-ingestion protocol for ONE registered
repro.index backend (any key from repro.index.available()) — the smoke path
for new backend plugins.
"""
from __future__ import annotations

import argparse
import json
import os
import sys


SECTIONS = ["table1_recall", "fig6_scaling", "fig7_breakdown", "fig8_ablation",
            "fig9_largescale", "table3_collisions", "appendix_hamming",
            "dist_scaling", "service_throughput", "search_mem", "insert_bench",
            "roofline", "churn_bench", "load_harness"]

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _emit_json(name: str, rows, quick: bool) -> None:
    """Write BENCH_<name>.json at the repo root (machine-readable twin of
    the CSV rows; `name` is the section or backend key)."""
    payload = {
        "benchmark": name,
        "quick": quick,
        "rows": [{"name": r[0], "us_per_call": r[1], "derived": r[2]}
                 for r in rows],
    }
    path = os.path.join(_REPO_ROOT, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")


def run_backend(name: str, quick: bool = False,
                query_chunk: int | None = None):
    """Continuous-ingestion benchmark of one registry backend: per-doc
    latency, stage breakdown, and recall vs the brute-force reference."""
    from benchmarks.common import build_pipeline, recall_fp, run_pipeline
    cycles, batch = (3, 256) if quick else (5, 512)
    ref_keep, _ = run_pipeline(build_pipeline("brute"),
                               cycles=cycles, batch=batch)
    keep, stats = run_pipeline(build_pipeline(name, query_chunk=query_chunk),
                               cycles=cycles, batch=batch)
    rec, fp = recall_fp(ref_keep, keep)
    last = stats[-1]
    us = last["wall"] / batch * 1e6
    # fused backends (hnsw_sharded) report one t_fused_step instead of the
    # per-stage split — print whichever timers the pipeline recorded
    keys = ["t_signature", "t_in_batch", "t_search", "t_insert"]
    if last.get("t_fused_step"):
        keys = ["t_signature", "t_fused_step"]
    parts = ";".join(f"{k[2:]}={last.get(k, 0.0) * 1e3:.0f}ms" for k in keys)
    return [(f"backend/{name}", round(us, 1),
             f"recall={rec:.3f};fp={fp:.4f};{parts};"
             f"admitted={int(keep.sum())}")]


def main() -> None:
    from repro.compile_cache import use_compile_cache
    from repro.index import available
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller corpora / fewer cycles")
    ap.add_argument("--only", default=None, choices=SECTIONS,
                    help="run one paper section")
    ap.add_argument("--backend", default=None, choices=sorted(available()),
                    help="benchmark one registered repro.index backend "
                         "instead of the paper sections "
                         f"(registered: {', '.join(sorted(available()))})")
    ap.add_argument("--query-chunk", type=int, default=None,
                    help="batched-search chunk for the --backend run "
                         "(unset = capacity-derived default, 0 = unchunked)")
    args = ap.parse_args()
    use_compile_cache()

    if args.backend:
        print("name,us_per_call,derived")
        rows = run_backend(args.backend, quick=args.quick,
                           query_chunk=args.query_chunk)
        for r in rows:
            print(",".join(str(x) for x in r), flush=True)
        _emit_json(f"backend_{args.backend}", rows, args.quick)
        return

    sections = [args.only] if args.only else SECTIONS
    print("name,us_per_call,derived")
    ok = True
    for name in sections:
        mod = __import__(f"benchmarks.{name}", fromlist=["run"])
        try:
            rows = mod.run(quick=args.quick)
            for r in rows:
                print(",".join(str(x) for x in r), flush=True)
            _emit_json(name, rows, args.quick)
        except Exception as e:  # keep the suite going; report the failure
            ok = False
            print(f"{name},ERROR,{type(e).__name__}:{e}", flush=True)
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
