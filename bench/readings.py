#!/usr/bin/env python3
"""Readings for the limits of `correct`, and the open-loop knee sweep.

    python3 bench/readings.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...] [--control K] [--fault NAME]
        [--rates R [R ...]] [--mix JSON]

Runs the cell once per seed in one process (the compiled programs are
shared; each run builds its own service, stream and prefill) and prints one
JSON line per run: the numbers `correct` compares, the end-to-end values
and the window's backlog at close. With --control K, the first K seeds also
put the control (bench/reference/control.py) in the program's place on the
same micro-batches and print its numbers. With --fault, every run has
that fault of bench/harness/faults.py planted under the timed path. With
--rates, an open-loop cell
runs once per rate (first seed) instead: the sweep that finds the knee.
--mix lays the JSON object's keys over the cell's mix (a fault that makes
the service faster needs a longer stream, `{"stream_docs_per_s": 2000}`).
Never part of a benchmark run; needs the chip like bench/run.py.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run as entry  # noqa: E402  (puts bench/ and src/ on sys.path)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--rates", type=float, nargs="*", default=[])
    ap.add_argument("--mix", type=json.loads, default={})
    args = ap.parse_args(argv)

    from harness.spec import load_cell
    cell = load_cell(args.workload, entry.ROOT)
    cell = dataclasses.replace(cell, mix={**cell.mix, **args.mix})
    entry._compile_cache()
    import jax
    dev = jax.devices()
    if dev[0].platform != "tpu" or len(dev) < cell.chips:
        print("readings: no TPU", file=sys.stderr)
        return 3
    from harness.faults import FAULTS
    from harness.runner import run_cell
    from reference import compare
    from reference.control import control_outcomes

    plan = ([(args.seeds[0], r) for r in args.rates] if args.rates
            else [(s, None) for s in args.seeds])
    for i, (seed, rate) in enumerate(plan):
        c = cell if rate is None else dataclasses.replace(
            cell, mix={**cell.mix, "phases": [
                {"share": 1.0, "rate_docs_per_s": rate}]})
        run = run_cell(c, seed, args.seconds, False, t_start=time.time(),
                       prepare=FAULTS[args.fault] if args.fault else None)
        print(json.dumps({"seed": seed, "rate": rate, "fault": args.fault,
                          "correct": run.result["correct"],
                          "numbers": run.numbers, "window": run.window}),
              flush=True)
        if i < args.control:
            order = [b["doc_ids"] for b in run.batches]
            outs = control_outcomes(run.bitmaps, order, run.tau,
                                    k=cell.config["fold"]["k"])
            nums = compare.compare(outs, len(run.bitmaps), run.bitmaps,
                                   run.ref_admitted, run.tau)
            print(json.dumps({"seed": seed, "control": nums}), flush=True)
    print(json.dumps({"readings_s": time.time() - T_START}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
