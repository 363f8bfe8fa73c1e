"""Beyond-paper: distributed-dedup scaling across index shards.

Drives the PROMOTED "hnsw_sharded" backend (repro.index) over the first
1/2/4/8 (--quick: 1/2/4) of this process's devices on the identical
stream and reports, per shard count:

  * insert-path throughput (docs/s through DedupPipeline.process_batch —
    the fused gather -> per-shard search -> pmax merge -> round-robin
    insert program), and
  * search-path throughput (queries/s through the read-only
    DedupPipeline.query merged top-k — the replica serving path),

plus admitted-count consistency: sharding the index must not change *what*
is admitted (recall-monotone merge, DESIGN.md §2), only how fast.

Every shard count runs in this one process: a device belongs to one
process at a time, so a child process could not reach devices the parent
already holds. On CPU, run with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (4 for --quick) for
virtual devices; they share one socket, so per-shard *work* (distance
evals per shard) is the proxy there: admitted counts must agree across
shard counts while the per-shard corpus shrinks ~linearly. Too few
devices for the largest count, or a shard count that fails, raises and
fails the section.
"""
from __future__ import annotations

import time

import numpy as np


def _one(nshards: int, cycles: int, batch: int):
    from repro.core.dedup import FoldConfig
    from repro.data import DATASET_PRESETS, SyntheticCorpus
    from repro.index import make_pipeline

    # total capacity is fixed across shard counts (per-shard = total/nshards)
    cfg = FoldConfig(capacity=8192 // nshards, M=12, M0=24,
                     ef_construction=32, ef_search=32, max_level=3,
                     threshold_space="minhash")
    pipe = make_pipeline("hnsw_sharded", cfg=cfg, shards=nshards)
    src = SyntheticCorpus(DATASET_PRESETS["common_crawl"])
    admitted, t_ins, probe = 0, 0.0, None
    for c in range(cycles):
        toks, lens, _ = src.next_batch(batch)
        if probe is None:
            probe = (toks, lens)
        t0 = time.perf_counter()
        keep, _ = pipe.process_batch(toks, lens)
        if c > 0:                       # drop the compile cycle
            t_ins += time.perf_counter() - t0
        admitted += int(np.asarray(keep).sum())
    # read-only merged-top-k search (replica serving path) on the first batch
    pipe.query(*probe)                  # compile
    t0 = time.perf_counter()
    for _ in range(3):
        pipe.query(*probe)
    t_q = (time.perf_counter() - t0) / 3
    return admitted, (cycles - 1) * batch / t_ins, batch / t_q


def run(quick: bool = False):
    import jax
    cycles, batch = (3, 256) if quick else (4, 512)
    shard_counts = (1, 2, 4) if quick else (1, 2, 4, 8)
    if len(jax.devices()) < shard_counts[-1]:
        raise RuntimeError(
            f"shard counts {shard_counts} need {shard_counts[-1]} devices, "
            f"this process has {len(jax.devices())}; on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={shard_counts[-1]} "
            f"before JAX starts")
    rows = []
    base_admitted = None
    for nshards in shard_counts:
        admitted, ins_tp, q_tp = _one(nshards, cycles, batch)
        if base_admitted is None:
            base_admitted = admitted
        drift = abs(admitted - base_admitted)
        rows.append((f"dist_scaling/shards={nshards}",
                     round(1e6 / ins_tp, 1),
                     f"insert_docs_per_s={ins_tp:.1f};"
                     f"search_docs_per_s={q_tp:.1f};"
                     f"admitted={admitted};admit_drift_vs_1shard={drift}"))
    return rows
