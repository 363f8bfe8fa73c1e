#!/usr/bin/env python3
"""A traced run of one cell, read with the phase readers.

    python3 bench/record_phases.py --workload <cell> --seed <n> \
        --seconds <s> [--keep <file.xplane.pb.gz>]

Runs the cell as `bench/run.py --trace 1` does, but reads the trace with
`harness.phases.load`, so that the result line also holds the readers of
bench/metrics/ that read the programs' scopes and the service's host spans
(PHASE_METRICS; BENCHMARK.json does not list them yet), the phases' share
of their programs, and the idle gaps named by the innermost span of either
kind. With --keep it also writes the trace cut down to two dispatch
intervals (see `cut`), with the "bench.window" span cut to match.
Never part of a benchmark run; needs the chip like bench/run.py.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import run as entry  # noqa: E402  (puts bench/ and src/ on sys.path)

PHASE_METRICS = ("search_descend_device_ms.ingest",
                 "search_beam_device_ms.ingest",
                 "insert_discover_device_ms.ingest",
                 "insert_merge_device_ms.ingest",
                 "insert_commit_device_ms.ingest",
                 "host_dispatch_ms.ingest",
                 "host_busy_ms.ingest")
# phases whose sum should account for their program's time
PARTS = {"insert_device_ms.ingest": ("insert_discover_device_ms.ingest",
                                     "insert_merge_device_ms.ingest",
                                     "insert_commit_device_ms.ingest"),
         "search_device_ms.ingest": ("search_descend_device_ms.ingest",
                                     "search_beam_device_ms.ingest")}


def cut(raw: bytes, steps: list[int]) -> bytes:
    """The trace cut to [steps[0], steps[2]] (1 ms of margin each side):
    the device's "XLA Modules" and "XLA Ops" lines, and on the host the
    "bench."/"fold." spans and the runtime's launches with what they
    hold."""
    from harness.phases import LAUNCH, xplane_pb2
    xs = xplane_pb2().XSpace()
    xs.ParseFromString(raw)
    a, b = (steps[0] - 1_000_000) * 1000, (steps[2] + 1_000_000) * 1000
    out = xplane_pb2().XSpace()
    for pl in xs.planes:
        device = pl.name.startswith("/device:")
        if not (device or pl.name.startswith("/host:CPU")):
            continue
        used: set[int] = set()
        for ln in pl.lines:
            base = ln.timestamp_ns * 1000
            names = [pl.event_metadata[e.metadata_id].name
                     for e in ln.events]
            launches = [(base + e.offset_ps,
                         base + e.offset_ps + e.duration_ps)
                        for e, n in zip(ln.events, names) if n == LAUNCH]
            keep = []
            for e, name in zip(ln.events, names):
                s, d = base + e.offset_ps, e.duration_ps
                if name == "bench.window":
                    e.offset_ps, e.duration_ps = a - base, b - a
                elif not a < s + d or not s < b:
                    continue
                elif device and ln.name not in ("XLA Modules", "XLA Ops"):
                    continue
                elif not device and not (
                        name.startswith(("bench.", "fold."))
                        or any(lo <= s and s + d <= hi
                               for lo, hi in launches)):
                    continue
                keep.append(e)
                used.add(e.metadata_id)
            del ln.events[:]
            ln.events.extend(keep)
        lines = [ln for ln in pl.lines if ln.events]
        del pl.lines[:]
        pl.lines.extend(lines)
        for mid in [m for m in pl.event_metadata if m not in used]:
            del pl.event_metadata[mid]
        out.planes.append(pl)
    return out.SerializeToString()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)

    import dataclasses

    from harness import phases
    from harness import trace as ht
    from harness.reduce import Context
    from harness.spec import _load_reader, load_cell
    cell = load_cell(args.workload, entry.ROOT)
    metrics = os.path.join(entry.ROOT, "bench", "metrics")
    extra = [({"name": n, "unit": "ms"},
              _load_reader(os.path.join(metrics, n + ".py"), n))
             for n in PHASE_METRICS]
    cell = dataclasses.replace(cell, per_layer=cell.per_layer + extra)
    entry._compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("record_phases: no TPU", file=sys.stderr)
        return 3

    kept: dict = {}
    close = ht.Capture.close

    def keep_and_close(cap):
        with open(cap.path(), "rb") as f:
            kept["raw"] = f.read()
        close(cap)

    ht.Capture.close = keep_and_close
    ht.load = phases.load           # runner reads the trace through this
    from harness.runner import run_cell
    run = run_cell(cell, args.seed, args.seconds, True, t_start=T_START)
    res = run.result
    got = {k: v["value"] for k, v in res["metrics"].items()}
    res["phase_share"] = {
        whole: sum(got[p] for p in parts) / got[whole]
        for whole, parts in PARTS.items()
        if whole in got and all(p in got for p in parts)}
    tr = phases.load(kept["raw"])
    ctx = Context(trace=tr, batches=[], mix=cell.mix, config=cell.config)
    res["idle_gaps_named"] = phases.idle_gaps(ctx)
    if args.keep:
        steps = sorted(s for n, s, _ in tr.host if n == "dedup_step"
                       and tr.window[0] <= s < tr.window[1])
        with gzip.open(args.keep, "wb") as f:
            f.write(cut(kept["raw"], steps))
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
