"""Faults planted under the timed path, for the check that `correct` sees
them: `bench/tests/test_bench_faults.py` on the CPU, and
`bench/readings.py --fault` on the chip at a cell's own size. Each takes
the built service before its prefill and breaks it on the instance.

The faults a one-chip dedup cell can have: an insert that leaves the
index unchanged, half of each batch left out, a verdict altered where it
is produced. (The exchange between chips has no one-chip cell.)
"""
from __future__ import annotations

import numpy as np

__all__ = ["FAULTS"]


def insert_unchanged(svc) -> None:
    svc.pipeline.backend.insert = lambda sig, keep, search_ids=None: None


def half_batch(svc) -> None:
    inner = svc.pipeline.dedup_step

    def step(sig, valid=None, timers=None):
        valid = np.asarray(valid).copy()
        valid[len(valid) // 2:] = False
        return inner(sig, valid=valid, timers=timers)

    svc.pipeline.dedup_step = step


def verdict_altered(svc) -> None:
    inner = svc.executor.on_outcome

    def record(out):
        out.keep = out.keep.copy()
        out.keep[0] = ~out.keep[0]
        return inner(out)

    svc.executor.on_outcome = record


FAULTS = {f.__name__: f for f in (insert_unchanged, half_batch,
                                  verdict_altered)}
