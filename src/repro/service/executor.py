"""Pipelined execution of dedup micro-batches via JAX async dispatch.

JAX device computations are futures: `pipe.signatures` and `pipe.dedup_step`
return without waiting for device execution, and the device queue runs them
in dispatch order. The naive `process_batch` loop throws that away by
calling `block_until_ready` after every stage (it must, to time them). The
executor instead dispatches batch i's whole graph, then immediately starts
batch i+1's host-side work — shingle prep, padding, dispatch — while batch
i's index search/insert is still executing. Results are materialized a fixed
`depth` batches behind the dispatch front, so the host is never more than
`depth` batches ahead (bounding live device memory) and never idle waiting
for a result it doesn't need yet.

The executor drives the generic `repro.index.DedupPipeline` surface —
`signatures(tokens, lengths) -> SigBatch` then `dedup_step(sig, valid)` —
so it serves ANY registered backend. Device-side backends (hnsw,
hnsw_sharded, hnsw_raw) overlap as described; host-side backends (dpk,
flat_lsh, prefix_filter, brute) synchronize inside their search and simply
run the same protocol without overlap.

Sequential-mode equivalence: the executor runs the exact same stage
functions against the same evolving index state in the same order, so its
keep-verdicts are bit-identical to a `process_batch` loop over the same
micro-batches (tested in tests/test_service.py).

Host spans (`jax.profiler.TraceAnnotation`, on the profiler's clock with
the device's programs): "fold.dispatch.signatures" and "fold.dispatch.step"
around the two dispatches, "fold.collect.wait" around the blocking
materialization, "fold.sync.timers" around a timed batch's wait for the
work before it; each carries the micro-batch's per-executor sequence
number as `batch`. Without a profiler the same host times land in the
`metrics` histograms "dispatch_ms" and "collect_wait_ms".
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Callable

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.index.pipeline import DedupPipeline
from repro.index.protocol import StepResult
from repro.service.batcher import MicroBatch
from repro.service.metrics import MetricsRegistry

__all__ = ["BatchOutcome", "PipelinedExecutor"]


@dataclasses.dataclass
class BatchOutcome:
    """Materialized (host-side) result of one micro-batch."""
    batch: MicroBatch
    keep: np.ndarray           # (B,) bool
    keep_in_batch: np.ndarray  # (B,) bool
    ids: np.ndarray            # (B, k) int32
    sims: np.ndarray           # (B, k) f32
    wall_s: float              # submit -> materialize (pipelined latency)
    stage_times: dict | None = None   # Fig. 7 per-stage seconds (sampled)
    seq: int = -1              # the executor's sequence number of the batch
    levels: np.ndarray | None = None  # (B,) HNSW levels its insert sampled


class PipelinedExecutor:
    """Depth-bounded pipeline over a DedupPipeline.

    on_outcome: optional callback invoked for every materialized batch in
    submission order (the service wires metrics + verdict recording here).
    depth=0 degenerates to fully synchronous execution (each submit blocks
    on its own result) — the comparison arm in benchmarks.

    timers_every=N (0 = never) runs every Nth submitted batch in blocking
    timer mode — the Fig. 7 per-stage breakdown (t_in_batch / t_search /
    t_insert, or t_fused_step) lands in that batch's
    BatchOutcome.stage_times. A timed batch cannot overlap (the per-stage
    walls require blocking between stages), so this is sampled profiling:
    one batch in every N pays the pipeline bubble, and first waits for the
    work in flight, so that its stage times hold its own work alone. The
    very first batch is never sampled — it pays XLA compilation (seconds),
    which would swamp the latency histograms with one absurd sample.

    metrics: optional registry for the host times of each batch —
    "dispatch_ms" (both dispatches; timed batches, which block, are left
    out) and "collect_wait_ms" (the blocking materialization).
    """

    def __init__(self, pipe: DedupPipeline, depth: int = 2,
                 on_outcome: Callable[[BatchOutcome], Any] | None = None,
                 timers_every: int = 0,
                 metrics: MetricsRegistry | None = None):
        self.pipe = pipe
        self.depth = max(int(depth), 0)
        self.on_outcome = on_outcome
        self.timers_every = max(int(timers_every), 0)
        self.metrics = metrics
        self._submitted = 0
        self._inflight: collections.deque[
            tuple[MicroBatch, StepResult, float, dict | None, int,
                  np.ndarray | None]] = collections.deque()

    @property
    def inflight(self) -> int:
        return len(self._inflight)

    @property
    def inflight_docs(self) -> int:
        """Valid docs dispatched but not yet materialized (backlog
        accounting for the bounded-admission check)."""
        return sum(entry[0].n_docs for entry in self._inflight)

    def submit(self, mb: MicroBatch) -> None:
        """Dispatch one micro-batch; may materialize older ones to keep the
        pipeline no more than `depth` deep."""
        seq = self._submitted
        timers = ({} if self.timers_every and seq > 0
                  and seq % self.timers_every == 0 else None)
        self._submitted += 1
        if timers is not None:
            # a timed batch times its own work alone: wait for the device
            # work still in flight (not collecting it, so a failure there
            # cannot take this batch down with it)
            with TraceAnnotation("fold.sync.timers", batch=seq):
                jax.block_until_ready([e[1] for e in self._inflight])  # foldlint: sync-ok(sampled timer mode blocks by design)
        t0 = time.perf_counter()
        with TraceAnnotation("fold.dispatch.signatures", batch=seq):
            sig = self.pipe.signatures(mb.tokens, mb.lengths)
        with TraceAnnotation("fold.dispatch.step", batch=seq):
            res = self.pipe.dedup_step(sig, valid=mb.valid, timers=timers)
        # the HNSW backends keep the levels they sampled on the host
        levels = getattr(self.pipe.backend, "last_levels", None)
        if self.metrics is not None and timers is None:
            self.metrics.observe("dispatch_ms",
                                 (time.perf_counter() - t0) * 1e3)
        self._inflight.append((mb, res, t0, timers, seq, levels))
        while len(self._inflight) > self.depth:
            self._collect_one()

    def drain(self) -> None:
        """Materialize everything still in flight."""
        while self._inflight:
            self._collect_one()

    def _collect_one(self) -> BatchOutcome:
        mb, res, t0, timers, seq, levels = self._inflight.popleft()
        # THE materialization point of the depth-k pipeline: by the time a
        # batch is collected here, its device work has had a full pipeline
        # depth to complete, so these blocks are overlap, not stalls
        tw = time.perf_counter()
        with TraceAnnotation("fold.collect.wait", batch=seq):
            keep = np.asarray(res.keep)  # foldlint: sync-ok(pipeline materialization point: verdicts leave the device here by design)
            keep_in_batch = np.asarray(res.keep_in_batch)  # foldlint: sync-ok(pipeline materialization point)
            ids = np.asarray(res.ids)  # foldlint: sync-ok(pipeline materialization point)
            sims = np.asarray(res.sims)  # foldlint: sync-ok(pipeline materialization point)
        now = time.perf_counter()
        if self.metrics is not None:
            self.metrics.observe("collect_wait_ms", (now - tw) * 1e3)
        out = BatchOutcome(batch=mb, keep=keep, keep_in_batch=keep_in_batch,
                           ids=ids, sims=sims, wall_s=now - t0,
                           stage_times=timers, seq=seq, levels=levels)
        if self.on_outcome is not None:
            self.on_outcome(out)
        return out
