"""Tiny sizes for CPU rehearsals of a cell (interpreted kernels)."""
from __future__ import annotations

import dataclasses
import json
import os
import time

from harness.runner import Run, run_cell
from harness.spec import load_cell

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SIZES = {"capacity": 2048,
         "fold": {"M": 8, "M0": 16, "ef_construction": 32, "ef_search": 32},
         "service": {"max_batch": 8, "max_len": 32},
         "corpus": {"max_len": 32, "mean_len": 24, "min_len": 8,
                    "window": 64},
         "prefill_docs": 48}

MIXES = {"closed": {"chunk_docs": 32, "backlog_docs": 32,
                    "stream_docs_per_s": 3000},
         "open": {"phases": [{"share": 1.0, "rate_docs_per_s": 40}],
                  "request_docs": 4}}

SEED = 2**31 + 11


def load_mix(traffic: str) -> dict:
    with open(os.path.join(ROOT, "bench", "mixes", traffic + ".json")) as f:
        return json.load(f)


def tiny_run(workload: str, seconds: float = 1.0, prepare=None,
             trace: bool = False, traffic: str | None = None,
             mix: dict | None = None) -> Run:
    """`workload` at tiny sizes; `traffic` puts another mix file of
    bench/mixes/ in the cell's place, and `mix` lays keys over the mix."""
    cell = load_cell(workload, ROOT)
    base = load_mix(traffic) if traffic else cell.mix
    cell = dataclasses.replace(
        cell, mix={**base, **MIXES[base["arrival"]], **(mix or {})})
    return run_cell(cell, SEED, seconds, trace, t_start=time.time(),
                    overrides=SIZES, prepare=prepare)
