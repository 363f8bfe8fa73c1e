#!/usr/bin/env python3
"""The chip benchmark of the online dedup service.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json on the chips of the machine it starts on:
set-up (stream from the seed, service, prefill, warm-up), a window of
`--seconds` under the cell's traffic mix, then the check of every answer
against the plain reference. The last line of stdout is one JSON object
(correct, attempted, failed, metrics, device, and with --trace 1 a
breakdown); the numbers compared for `correct` are also the last lines of
stderr, each beside its limit. Without a TPU, with fewer chips than the cell
asks for, or without the system under test (src/repro), it exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]


def _compile_cache() -> str:
    """JAX's persistent compilation cache, always <checkout>/.jax_cache: a
    fixed path inside the checkout, so that only a checkout's first run
    compiles and two checkouts share nothing. Every program is cached,
    however quick its compile."""
    import jax
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness.spec import load_cell
    try:
        cell = load_cell(args.workload, ROOT)
    except (FileNotFoundError, KeyError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("bench: the system under test (src/repro) is not here",
              file=sys.stderr)
        return 2
    cache = _compile_cache()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"sees {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 3
    print(f"devices: {len(devs)} x {devs[0].device_kind}; compile cache "
          f"{cache}", flush=True)

    from harness.runner import print_checks, run_cell
    run = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   t_start=T_START)
    print_checks(run.result["checks"])
    print(json.dumps(run.result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
