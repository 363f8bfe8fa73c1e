"""One generic online-dedup pipeline over any registered backend.

Owns the shared steps of the paper's workflow (§4.1, Fig 3): ① signature
generation (driven by the backend's SigSpec), ② in-batch cleanup (greedy
leader sweep over the backend's similarity matrix), ④ the threshold filter,
and the Fig. 7 per-stage timers; the backend contributes ③ search and
⑤ insert plus the capacity/snapshot lifecycle.

Like the original FoldPipeline, the workflow is split into two reusable
stage functions — `signatures` (step ①, host prep + device dispatch) and
`dedup_step` (steps ②-⑤) — so the serving layer (repro.service.executor)
can pipeline batch i+1's signature prep under batch i's search/insert via
JAX async dispatch. `process_batch` composes the two with blocking
per-stage timers, preserving the Fig. 7 breakdown. Host-side backends
(DPK, flat LSH, prefix filter) synchronize inside `search`; the surface is
identical, they just don't overlap.
"""
from __future__ import annotations

import functools
import inspect
import time
from typing import TYPE_CHECKING, Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.index.protocol import (BATCH_FIRST, INDEX_FIRST, DedupBackend,
                                  SigBatch, StepResult)

if TYPE_CHECKING:
    from repro.index.exact import ExactDupFilter

__all__ = ["DedupPipeline", "QueryResult", "greedy_leader",
           "greedy_leader_split"]


class QueryResult(NamedTuple):
    """Read-only search verdicts (DedupPipeline.query — nothing inserted).

    is_dup     (B,) bool  — some corpus doc matches at >= tau_index
    ids        (B, k) int32 — retrieved neighbor ids (-1 = none; column 0
                is the exact-match ref id for exact_hit rows)
    sims       (B, k) f32 — similarities (1.0 in column 0 for exact hits)
    exact_hit  (B,) bool  — verdict served by the exact-dup filter
    """
    is_dup: Any
    ids: Any
    sims: Any
    exact_hit: Any


@functools.partial(jax.jit, static_argnames=("tau",))
def _greedy_sweep(sim: jnp.ndarray, tau: float,
                  eligible: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact sequential greedy-leader over a (B, B) similarity matrix.

    keep[i] = eligible[i] and no kept j < i with sim[i, j] >= tau;
    hit[i]  = some kept j < i has sim[i, j] >= tau (the in-batch-duplicate
    flag, tracked separately so ineligible docs are still labeled).
    O(B) fori over rows."""
    B = sim.shape[0]
    idx = jnp.arange(B)

    def body(i, carry):
        keep, hit = carry
        h = jnp.any((sim[i] >= tau) & keep & (idx < i))
        return keep.at[i].set(eligible[i] & ~h), hit.at[i].set(h)

    init = (jnp.zeros((B,), jnp.bool_), jnp.zeros((B,), jnp.bool_))
    return jax.lax.fori_loop(0, B, body, init)


def greedy_leader(sim: Any, tau: float,
                  eligible: Any = None) -> jnp.ndarray:
    """Step ②: keep-mask for in-batch dedup (public since PR 2).

    eligible (B,) bool — docs that may be kept at all; ineligible docs are
    never leaders (used for INDEX_FIRST / join-style admission where corpus
    duplicates are excluded before the sweep). Default: all eligible."""
    return greedy_leader_split(sim, tau, eligible)[0]


def greedy_leader_split(sim: Any, tau: float,
                        eligible: Any = None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """greedy_leader plus the in-batch-duplicate flag: (keep, batch_hit)."""
    sim = jnp.asarray(sim)
    if eligible is None:
        eligible = jnp.ones((sim.shape[0],), jnp.bool_)
    return _greedy_sweep(sim, float(tau), jnp.asarray(eligible))


def _ready(x: Any) -> None:
    """Block on a device array; no-op for host (numpy) results."""
    if hasattr(x, "block_until_ready"):
        x.block_until_ready()


class DedupPipeline:
    """Host-side orchestration of online dedup over an evolving corpus.

    Composes the shared signature stage + in-batch cleanup with any
    `repro.index.protocol.DedupBackend`; lifecycle calls (`grow`, `save`,
    `restore`, `capacity`, `inserted`, `stats_schema`) delegate to the
    backend, so the serving layer's growth watermark and snapshot rotation
    work for every registered backend."""

    def __init__(self, backend: DedupBackend):
        # deferred: repro.core's package init imports repro.index (the
        # FoldPipeline re-export), so core modules load lazily here
        from repro.core.hashing import hash_seeds
        self.backend = backend
        spec = backend.sig_spec
        self._spec = spec
        self._seeds = (hash_seeds(spec.num_hashes, spec.seed)
                       if ({"sigs", "bitmaps"} & spec.needs) else None)
        # extended insert contract (search reuse): only pass the step-③
        # neighbor ids to backends whose insert declares the parameter, so
        # third-party backends written against the old 2-arg surface keep
        # working unchanged
        try:
            self._insert_takes_search_ids = ("search_ids" in inspect
                                             .signature(backend.insert)
                                             .parameters)
        except (TypeError, ValueError):
            self._insert_takes_search_ids = False
        # exact-dup short-circuit front-end (repro.index.exact): opt-in via
        # the shared config's exact_filter flag; None when off. The filter
        # is consulted by process_batch/query here and by the service's
        # submit-time front door — same object, shared state.
        self.exact: "Optional[ExactDupFilter]" = None
        if getattr(getattr(backend, "cfg", None), "exact_filter", False):
            from repro.index.exact import ExactDupFilter
            self.exact = ExactDupFilter()

    # -- lifecycle (delegated) ----------------------------------------------
    @property
    def capacity(self) -> int:
        return self.backend.capacity

    @property
    def inserted(self) -> int:
        return self.backend.inserted

    def grow(self, new_capacity: int) -> "DedupPipeline":
        self.backend.grow(new_capacity)
        return self

    # deletion lifecycle (protocol DELETION CONTRACT; raises
    # NotImplementedError for backends with supports_deletion=False).
    # getattr defaults keep pre-contract structural backends working: they
    # read as deletion-free rather than AttributeError-ing.
    @property
    def deleted(self) -> int:
        return getattr(self.backend, "deleted", 0)

    @property
    def dead_fraction(self) -> float:
        return getattr(self.backend, "dead_fraction", 0.0)

    def delete(self, ids: Any) -> int:
        fn = getattr(self.backend, "delete", None)
        if fn is None:
            raise NotImplementedError(
                f"backend {self.backend.name!r} does not support deletion "
                f"(supports_deletion=False)")
        return fn(ids)

    def compact(self) -> dict:
        fn = getattr(self.backend, "compact", None)
        return fn() if fn is not None else {"reclaimed": 0}

    def save(self, ckpt_dir: str, step: int,
             async_write: bool = False) -> None:
        self.backend.save(ckpt_dir, step, async_write=async_write)
        if self.exact is not None:
            # sidecar is host-cheap and loss-safe (the fuzzy path backstops
            # exact dups), so it is written synchronously even when the
            # backend's array checkpoint goes out async
            self.exact.save(ckpt_dir, step)

    def restore(self, ckpt_dir: str, step: int | None = None) -> int:
        step = self.backend.restore(ckpt_dir, step)
        if self.exact is not None:
            self.exact.load(ckpt_dir, step)
        return step

    def stats_schema(self) -> tuple[str, ...]:
        extra = ("n_exact_hits",) if self.exact is not None else ()
        return (("t_signature", "t_in_batch", "t_search", "t_insert",
                 "n_batch_drop", "n_index_drop", "n_insert", "n_overflow",
                 "count") + extra + tuple(self.backend.stats_schema()))

    # -- step ① -------------------------------------------------------------
    def signatures(self, tokens: Any, lengths: Any) -> SigBatch:
        """shingle → (MinHash → bitmap) per the backend's SigSpec.

        Dispatches device work and returns immediately (arrays are futures
        under JAX async dispatch)."""
        from repro.core import bitmap as bm
        from repro.core.shingle import shingle_hashes
        from repro.kernels import ops
        spec = self._spec
        with TraceAnnotation("fold.signatures.shingle"):
            sh = shingle_hashes(jnp.asarray(tokens, jnp.uint32),
                                jnp.asarray(lengths, jnp.int32),
                                spec.shingle_n)
        sigs = bitmaps = pcs = None
        if self._seeds is not None:
            sigs = ops.minhash(sh, self._seeds, use_kernel=spec.use_kernel)
        if "bitmaps" in spec.needs:
            bitmaps = bm.pack_bitmaps(sigs, T=spec.T)
            pcs = bm.popcount(bitmaps)
        return SigBatch(sigs=sigs, bitmaps=bitmaps, pcs=pcs,
                        shingles=sh if "shingles" in spec.needs else None)

    def _insert(self, sig: SigBatch, keep: Any, search_ids: Any) -> Any:
        """Step ⑤ with the extended search-reuse contract (see protocol)."""
        if self._insert_takes_search_ids:
            return self.backend.insert(sig, keep, search_ids=search_ids)
        return self.backend.insert(sig, keep)

    # -- steps ②-⑤ ----------------------------------------------------------
    def dedup_step(self, sig: SigBatch, valid: Any = None,
                   timers: dict[str, Any] | None = None) -> StepResult:
        """In-batch cleanup, index search, threshold filter, admit uniques.

        valid: optional (B,) bool — False rows are shape padding from the
        micro-batcher: they take part in nothing observable (padding rows
        sit at the END of the batch, so the greedy in-batch sweep cannot
        drop a real doc on their account) and are never admitted.

        timers: pass a dict to run in blocking mode — per-stage wall-clock
        is recorded under t_in_batch / t_search / t_insert (Fig. 7 hooks).
        Without it the step is dispatched as asynchronously as the backend
        allows, letting the executor overlap the next batch's signature
        stage with this step's device execution.

        Host spans "fold.step.in_batch", "fold.step.search" and
        "fold.step.insert" cover each stage's host work and dispatch (the
        insert's includes the backend's level sampling and slot guard).
        """
        be = self.backend
        fused = getattr(be, "fused_step", None)
        if fused is not None:
            if timers is not None:
                timers.setdefault("t_in_batch", 0.0)
                timers.setdefault("t_search", 0.0)
                timers.setdefault("t_insert", 0.0)
                t0 = time.perf_counter()
                res = fused(sig, valid=valid)
                _ready(res.keep)
                timers["t_fused_step"] = time.perf_counter() - t0
                return res
            return fused(sig, valid=valid)
        if be.order == BATCH_FIRST:
            return self._step_batch_first(sig, valid, timers)
        assert be.order == INDEX_FIRST, be.order
        return self._step_index_first(sig, valid, timers)

    def _step_batch_first(self, sig: SigBatch, valid: Any,
                          timers: dict[str, Any] | None) -> StepResult:
        be = self.backend
        block = timers is not None

        t0 = time.perf_counter()
        with TraceAnnotation("fold.step.in_batch"):
            keep_in_batch = greedy_leader(be.batch_sim(sig), be.tau_batch)
            if block:
                _ready(keep_in_batch)
                timers["t_in_batch"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        with TraceAnnotation("fold.step.search"):
            ids, sims = be.search(sig)
            dup_index = (sims >= be.tau_index).any(axis=-1)
            if block:
                _ready(dup_index)
                timers["t_search"] = time.perf_counter() - t0

        keep = keep_in_batch & ~jnp.asarray(dup_index)
        if valid is not None:
            keep = keep & jnp.asarray(valid)

        t0 = time.perf_counter()
        with TraceAnnotation("fold.step.insert"):
            handle = self._insert(sig, keep, ids)
            if block:
                if handle is not None:   # device insert: charge t_insert
                    _ready(handle)
                timers["t_insert"] = time.perf_counter() - t0
        return StepResult(keep=keep, keep_in_batch=keep_in_batch,
                          ids=ids, sims=sims)

    def _step_index_first(self, sig: SigBatch, valid: Any,
                          timers: dict[str, Any] | None) -> StepResult:
        be = self.backend
        block = timers is not None

        t0 = time.perf_counter()
        ids, sims = be.search(sig)
        dup_index = np.asarray((sims >= be.tau_index).any(axis=-1))
        if block:
            timers["t_search"] = time.perf_counter() - t0

        eligible = ~dup_index
        if valid is not None:
            eligible = eligible & np.asarray(valid)

        t0 = time.perf_counter()
        if hasattr(be, "in_batch_keep"):
            keep, hit = be.in_batch_keep(sig, eligible)
        else:
            keep, hit = greedy_leader_split(be.batch_sim(sig), be.tau_batch,
                                            eligible)
        if block:
            _ready(keep)
            timers["t_in_batch"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        handle = self._insert(sig, keep, ids)
        if block:
            if handle is not None:
                _ready(handle)
            timers["t_insert"] = time.perf_counter() - t0
        return StepResult(keep=keep, keep_in_batch=~np.asarray(hit),
                          ids=ids, sims=sims)

    def _exact_hits(self, tokens: Any, lengths: Any
                    ) -> Tuple[Any, np.ndarray, np.ndarray]:
        """(hashes, hit, refs) for the exact front door; hit marks rows
        whose content hash is already in the filter OR appeared earlier in
        this batch (same hash → same signature → same eventual verdict, so
        short-circuiting is verdict-preserving either way)."""
        from repro.index.exact import batch_hashes
        hashes = batch_hashes(tokens, lengths)
        B = len(hashes)
        hit = np.zeros(B, bool)
        refs = np.full(B, -1, np.int64)
        seen: set[int] = set()
        for i, h in enumerate(hashes):
            r = self.exact.lookup(h)
            if r is not None:
                hit[i] = True
                refs[i] = r
            elif h in seen:
                hit[i] = True
            else:
                seen.add(h)
        return hashes, hit, refs

    def process_batch(self, tokens: Any,
                      lengths: Any) -> tuple[np.ndarray, dict]:
        """Dedup one incoming batch. Returns (keep_mask (B,), stats).

        Blocking composition of the two stage functions; per-stage timing
        and admit/drop accounting preserved for the Fig. 7 breakdown. With
        the exact-dup front end on (FoldConfig.exact_filter), content-hash
        hits are dropped before signature generation — an all-hit batch
        pays no device work at all."""
        stats: dict[str, Any] = {}
        # pre-batch occupancy (host sync — process_batch is the blocking
        # path): lets the overflow check below compare claimed admissions
        # against rows the backend actually landed
        count0 = self.backend.inserted

        hashes = None
        B = np.asarray(tokens).shape[0]
        hit = np.zeros(B, bool)
        if self.exact is not None:
            hashes, hit, _refs = self._exact_hits(tokens, lengths)
            n_hit = int(hit.sum())
            if n_hit:
                self.exact.record_hit(n_hit)
            stats["n_exact_hits"] = n_hit
            if hit.all():
                # verbatim-replay fast path: no signatures, no search
                for key in ("t_signature", "t_in_batch", "t_search",
                            "t_insert"):
                    stats[key] = 0.0
                stats.update(n_batch_drop=0, n_index_drop=0, n_insert=0,
                             count=count0, n_overflow=0)
                return np.zeros(B, bool), stats

        t0 = time.perf_counter()
        sig = self.signatures(tokens, lengths)
        for a in reversed(sig):
            if a is not None:
                _ready(a)
                break
        stats["t_signature"] = time.perf_counter() - t0

        res = self.dedup_step(sig, valid=(~hit if hit.any() else None),
                              timers=stats)

        keep = np.asarray(res.keep)
        keep_in_batch = np.asarray(res.keep_in_batch)
        if hashes is not None:
            for i in np.flatnonzero(keep):
                self.exact.add(hashes[int(i)])
        stats["n_batch_drop"] = int((~keep_in_batch & ~hit).sum())
        stats["n_index_drop"] = int((keep_in_batch & ~keep & ~hit).sum())
        stats["n_insert"] = int(keep.sum())
        stats["count"] = self.backend.inserted
        # rows whose verdict claims admission but which the backend did not
        # land (fixed-capacity overflow). Every built-in backend refuses the
        # batch instead (so this stays 0); the stat catches third-party
        # backends that silently drop.
        stats["n_overflow"] = max(
            0, stats["n_insert"] - (stats["count"] - count0))
        return keep, stats

    # -- read-only query (the replica / router surface) ---------------------
    def query(self, tokens: Any, lengths: Any = None) -> QueryResult:
        """Search-only "is this a dup?" verdicts — NOTHING is inserted.

        This is the read-replica serving surface (repro.cluster): exact
        front-door hits (when configured) skip the search entirely; other
        rows pay step ① + step ③ against the current corpus and the
        tau_index threshold. Host-synchronous by design — callers are
        latency-measuring serving paths, not the pipelined admission loop.
        """
        toks = np.asarray(tokens)
        B = toks.shape[0]
        if lengths is None:
            lengths = np.full(B, toks.shape[1], np.int32)
        hit = np.zeros(B, bool)
        refs = np.full(B, -1, np.int64)
        if self.exact is not None:
            _hashes, hit, refs = self._exact_hits(toks, lengths)
            if hit.any():
                self.exact.record_hit(int(hit.sum()))
        k = max(1, int(getattr(getattr(self.backend, "cfg", None),
                               "k", 1) or 1))
        if B and hit.all():
            ids = np.full((B, k), -1, np.int32)
            ids[:, 0] = refs.astype(np.int32)
            sims = np.zeros((B, k), np.float32)
            sims[:, 0] = 1.0
            return QueryResult(is_dup=np.ones(B, bool), ids=ids, sims=sims,
                               exact_hit=hit)
        sig = self.signatures(toks, lengths)
        ids, sims = self.backend.search(sig)
        ids = np.asarray(ids, np.int32).copy()
        sims = np.asarray(sims, np.float32).copy()
        is_dup = np.asarray((sims >= self.backend.tau_index).any(axis=-1))
        if hit.any():
            is_dup = is_dup | hit
            ids[hit, 0] = refs[hit].astype(np.int32)
            sims[hit, 0] = 1.0
        return QueryResult(is_dup=is_dup, ids=ids, sims=sims, exact_hit=hit)
