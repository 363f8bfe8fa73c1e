"""HNSW-organized backends: FOLD's bitmap index and the raw-metric FAISS
analogues (paper §3.2, §4) behind the `repro.index` protocol.

Both share core/hnsw.py's functional index machinery; what differs is the
vertex representation and distance — exactly the contribution the paper's
FAISS baselines isolate:

  HNSWBitmapBackend ("hnsw")    (T//32,) packed one-hot-folded bitmaps,
                                bitmap-Jaccard via the Pallas kernel
  RawHNSWBackend   ("hnsw_raw") (H,) raw MinHash lanes with the naive
                                metric (minhash_jaccard | hamming)
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.analysis.programs import (ProgramBudget, ProgramSpec,
                                     register_programs)
from repro.core.dedup import FoldConfig, bitmap_tau
from repro.core.hnsw import (HNSWConfig, HNSWState, abstract_state,
                             hnsw_compact, hnsw_delete, hnsw_grow, hnsw_init,
                             hnsw_insert_batch, hnsw_search, sample_levels)
from repro.index.protocol import BATCH_FIRST, DedupBackend, SigBatch, SigSpec
from repro.index.registry import register
from repro.kernels import ops

__all__ = ["HNSWBitmapBackend", "RawHNSWBackend"]


@jax.jit
def _live_count(node_level, dead):
    """Admitted-minus-deleted occupancy as ONE cached device program.

    The eager form (`jnp.sum((node_level >= 0) & ~dead)`) dispatched three
    separate device ops per poll; the growth watermark and pipeline stats
    poll this every batch, so keep it a single fused reduction."""
    return jnp.sum((node_level >= 0) & ~dead, dtype=jnp.int32)


class _HNSWLifecycle(DedupBackend):
    """Shared functional-HNSW capacity lifecycle + overflow refusal +
    deletion (tombstones, free-slot reuse, online compaction).

    Subclasses provide `cfg` (FoldConfig), `hnsw_cfg`, `state`, and a
    `_batches` level-seed counter; hooks cover any side containers that
    must track capacity (the bitmap backend's exact-verify sig store)."""

    cfg: FoldConfig
    hnsw_cfg: HNSWConfig
    state: HNSWState
    _batches: int
    # host copy of the levels sampled for the latest insert (the serving
    # executor pairs it with its batch for the commit_links histogram)
    last_levels: np.ndarray | None = None

    # sync-free occupancy upper bound (mirrors ShardedDedupBackend): the
    # true count is a device scalar, so we only pay a host sync when the
    # bound says the incoming batch might not fit
    _known_count: int = 0
    _dispatched_bound: int = 0

    # -- capability flags: every registered backend declares all four
    # explicitly (foldlint F121) so a deleted/renamed flag is visible drift,
    # not a silent fall-through to the protocol defaults
    supports_growth = True
    supports_snapshots = True
    supports_deletion = True
    track_slots = False

    # -- deletion state (protocol DELETION CONTRACT) -------------------------
    _n_deleted = 0        # cumulative successful deletes (process lifetime)
    _n_dead = 0           # live tombstones awaiting compact (host-exact)
    _t_compact = 0.0      # cumulative compact() wall seconds
    _free: list | None = None    # reclaimed slot ids (host free list)
    _count_hw: int | None = None  # host mirror of state.count (slot logging)

    # -- overflow refusal ----------------------------------------------------
    def _guard_capacity(self, keep, offered: int = 0) -> None:
        """Refuse an insert that could overflow the fixed-capacity index.

        hnsw_insert_batch silently skips rows once full — acceptable for the
        raw primitive, but a protocol backend must never return a keep-mask
        whose verdicts claim admission for dropped rows. Standalone (non-
        IndexManager) use therefore fails loudly here; under the service the
        growth watermark re-allocates ahead of this guard ever tripping.

        The sync-free bound charges the KEPT-row count whenever the mask is
        already host-resident (numpy), and only the full batch size B for a
        device mask (reading it would force the very host sync the bound
        exists to avoid). Charging B for host masks used to burn the last
        ~B slots of headroom instantly, forcing a host sync on every batch
        right where the growth watermark needs the pipeline to stay async.
        After a sync the exact kept count is known, so only that is charged.

        The serving pipeline passes DEVICE masks, so near capacity it still
        pays the conservative B charge per batch; what keeps that path
        sync-free in practice is the IndexManager growth watermark (its own
        host-side dispatch accounting grows the index at ~85% occupancy,
        long before this bound can shrink below one batch) plus grow()
        re-deriving known/bound right after each re-allocation. The
        host-mask fast path covers direct/host-side callers.

        `offered` is the number of reclaimed free slots handed to this
        insert (hnsw_insert_batch free_slots): rows landing in a free slot
        consume no fresh capacity, so only max(0, charge - offered) counts
        against the HIGH-WATER bound (anchored on state.count, not the live
        count — dead slots still occupy capacity until compact()).
        """
        cap = self.hnsw_cfg.capacity
        if isinstance(keep, np.ndarray):
            charge = int(keep.sum())           # host mask: exact, sync-free
        else:
            charge = int(keep.shape[0])        # device mask: conservative B
        fresh = max(0, charge - offered)
        if self._known_count + self._dispatched_bound + fresh <= cap:
            self._dispatched_bound += fresh
            return
        with TraceAnnotation("fold.sync.capacity"):
            self._known_count = int(self.state.count)  # foldlint: sync-ok(rare re-anchor: only when the sync-free bound says the batch might not fit)
            n_keep = int(np.asarray(keep).sum())  # foldlint: sync-ok(already syncing to re-anchor; exact kept count is free here)
        self._dispatched_bound = 0
        fresh = max(0, n_keep - offered)
        if self._known_count + fresh > cap:
            raise RuntimeError(
                f"HNSW index full: {self._known_count} of {cap} slots used "
                f"and the batch admits {fresh} beyond the free list; call "
                f"grow() — or compact() if tombstones are pending — (or run "
                f"under the service's IndexManager growth watermark) before "
                f"inserting — refusing to silently drop admitted docs")
        self._dispatched_bound = fresh

    # -- search reuse --------------------------------------------------------
    def _seeds_from(self, search_ids):
        """Step-③ neighbor ids -> batched-insert discovery seeds.

        Consulted only when the batched two-phase insert is active and
        cfg.reuse_search is on; the per-doc path and reuse_search=False
        rebuild graphs without any dependence on the admission search
        (the bit-identity reference configurations)."""
        if (search_ids is None or not self.hnsw_cfg.batched_insert
                or not getattr(self.cfg, "reuse_search", True)):
            return None
        return jnp.asarray(search_ids, jnp.int32)

    # -- levels --------------------------------------------------------------
    def _sample_levels(self, B: int) -> jnp.ndarray:
        """The next batch's pre-sampled levels, kept on the host too."""
        self.last_levels = sample_levels(
            B, self.hnsw_cfg, seed=self._batches + self.cfg.seed + 1)
        self._batches += 1
        return jnp.asarray(self.last_levels)

    # -- occupancy -----------------------------------------------------------
    @property
    def inserted(self) -> int:
        """LIVE document count: admitted - deleted (host sync: reads a
        device reduction). Capacity accounting (growth watermark, pipeline
        occupancy) therefore sees reclaimed space; the overflow guard keeps
        its own HIGH-WATER anchor because dead slots still hold capacity
        until compact() free-lists them."""
        return int(_live_count(self.state.node_level,  # foldlint: sync-ok(occupancy poll; one fused cached program)
                               self.state.dead))

    # -- deletion / compaction (protocol DELETION CONTRACT) ------------------
    @property
    def deleted(self) -> int:
        return self._n_deleted

    @property
    def dead_fraction(self) -> float:
        # host-exact tombstone counter: no device sync (polled every batch)
        return self._n_dead / max(self.hnsw_cfg.capacity, 1)

    def delete(self, ids) -> int:  # foldlint: cold-path
        """Tombstone slot ids (idempotent; see protocol.py). The device
        delete is O(D); slots become reusable only after compact()."""
        ids = np.unique(np.asarray(ids, np.int64).reshape(-1))
        ids = ids[(ids >= 0) & (ids < self.hnsw_cfg.capacity)]
        if len(ids) == 0:
            return 0
        # pad to the next power of two for stable compiled shapes
        D = 1 << int(len(ids) - 1).bit_length() if len(ids) > 1 else 1
        pad = np.full(D, -1, np.int64)
        pad[:len(ids)] = ids
        self.state, n_dev = hnsw_delete(self.hnsw_cfg, self.state,
                                        jnp.asarray(pad, jnp.int32))
        n = int(n_dev)                          # host sync
        self._n_deleted += n
        self._n_dead += n
        return n

    def compact(self) -> dict:  # foldlint: cold-path
        """Repair adjacency around tombstones, unlink them, and re-derive
        the host free list from the device state (host sync — callers
        schedule this off the hot path, e.g. repro.lifecycle's watermark)."""
        t0 = time.perf_counter()
        self.state, n_dev = hnsw_compact(self.hnsw_cfg, self.state)
        reclaimed = int(n_dev)
        node_level = np.asarray(self.state.node_level)
        count = int(self.state.count)
        # every unlinked slot below the high-water mark is reusable —
        # including any previously popped-but-unconsumed free slots
        self._free = [int(i) for i in np.flatnonzero(node_level[:count] < 0)]
        self._n_dead = 0
        self._count_hw = count
        self._known_count = count               # re-anchor overflow guard
        self._dispatched_bound = 0
        self._t_compact += time.perf_counter() - t0
        return {"reclaimed": reclaimed, "free": len(self._free),
                "t_compact": self._t_compact}

    def _prepare_slots(self, keep, B: int):
        """Overflow guard + free-list pop for one insert.

        Guards FIRST (a refusal must not leak free slots), then pops up to
        B reclaimed slots for the device to consume before fresh capacity.
        Popped-but-unconsumed slots (fewer kept rows than offered frees)
        are temporarily orphaned — the next compact() re-derives the free
        list from the device state and recovers them. Returns
        (free_dev (B,) int32 | None, free_host list)."""
        free = self._free if self._free else []
        offered = min(B, len(free))
        self._guard_capacity(keep, offered=offered)
        if offered == 0:
            return None, []
        take, self._free = free[:offered], free[offered:]
        pad = np.full(B, -1, np.int32)
        pad[:offered] = take
        return jnp.asarray(pad), take

    def _log_slots(self, keep, free_host):
        """Host mirror of the device slot assignment for one insert: the
        j-th kept row lands in free_host[j] while frees last, then in
        consecutive fresh slots from the pre-insert high-water count.
        Returns (order, slots): kept-row indices and their slot ids.

        Host-syncs `keep`; the count mirror syncs once (first logged
        insert / after restore or compact) and is advanced host-side."""
        order = np.flatnonzero(np.asarray(keep))  # foldlint: sync-ok(slot logging is opt-in; lifecycle needs the host mask)
        if self._count_hw is None:
            self._count_hw = int(self.state.count)  # foldlint: sync-ok(one-time count-mirror seed; advanced host-side after)
        t = min(len(order), len(free_host))
        slots = np.concatenate([
            np.asarray(free_host[:t], np.int64),  # foldlint: sync-ok(host free-list bookkeeping)
            self._count_hw + np.arange(len(order) - t, dtype=np.int64),
        ]).astype(np.int32)
        self._count_hw += len(order) - t
        return order, slots

    def _record_insert(self, sig, keep, free_host) -> None:
        """Slot-dependent host bookkeeping for one insert: the exact-verify
        sig store scatter and the track_slots log. No-op (and sync-free)
        when neither is active."""
        sig_store = getattr(self, "_sig_store", None)
        if sig_store is None and not self.track_slots:
            self._count_hw = None       # host count mirror goes stale
            return
        order, slots = self._log_slots(keep, free_host)
        if sig_store is not None:
            sig_store[slots] = np.asarray(sig.sigs)[order]  # foldlint: sync-ok(exact-verify sig store is host-resident by design)
        if self.track_slots:
            q = list(getattr(self, "_slots_q", []))
            q.append(slots)
            self._slots_q = q

    # -- hooks ---------------------------------------------------------------
    def _after_grow(self, new_capacity: int) -> None:
        pass

    def _reset_containers(self, capacity: int) -> None:
        """Rebuild side containers at a snapshot's (smaller) capacity."""

    def _extra_tree(self) -> dict:
        """Extra checkpoint leaves beyond {state, batches}."""
        return {}

    def _take_extra(self, got: dict) -> None:
        pass

    # -- lifecycle -----------------------------------------------------------
    def grow(self, new_capacity: int) -> None:  # foldlint: cold-path
        """Re-pad the index to a larger capacity (graph preserved exactly).

        Recompiles search/insert once per growth; the geometric growth
        policy lives in repro.service.index_manager."""
        self.hnsw_cfg, self.state = hnsw_grow(self.hnsw_cfg, self.state,
                                              new_capacity)
        self.cfg = dataclasses.replace(self.cfg, capacity=new_capacity)
        self._after_grow(new_capacity)
        # growth already pays a recompile, so one host sync is cheap here:
        # re-derive the sync-free occupancy bound instead of carrying the
        # accumulated over-charges into the new capacity window (high-water
        # anchor: dead slots occupy capacity until compact)
        self._known_count = int(self.state.count)
        self._dispatched_bound = 0

    def save(self, ckpt_dir: str, step: int, async_write: bool = False):  # foldlint: cold-path
        """Checkpoint the evolving index (HNSWState is a pytree).

        async_write=True snapshots to host synchronously and writes in a
        background thread (checkpoint.save_async) — the serving layer uses
        this so periodic snapshots don't stall the dispatch pipeline on
        disk I/O. Callers order writes with checkpoint.wait_pending()."""
        from repro.train import checkpoint as ckpt
        tree = {"state": self.state, "batches": jnp.int32(self._batches)}
        tree.update(self._extra_tree())
        writer = ckpt.save_async if async_write else ckpt.save
        writer(ckpt_dir, step, tree,
               extra={"capacity": self.hnsw_cfg.capacity})

    def restore(self, ckpt_dir: str, step: int | None = None) -> int:  # foldlint: cold-path
        from repro.train import checkpoint as ckpt
        step = ckpt.latest_step(ckpt_dir) if step is None else step
        if step is None:     # a bare assert would vanish under python -O
            raise FileNotFoundError(
                f"no committed checkpoint found in {ckpt_dir!r}")
        meta = ckpt.manifest(ckpt_dir, step)
        cap = int(meta.get("capacity", self.hnsw_cfg.capacity))
        target = max(cap, self.hnsw_cfg.capacity)
        if cap != self.hnsw_cfg.capacity:
            # rebuild containers at the snapshot's capacity so array shapes
            # match the checkpoint (a snapshot may be smaller than the
            # configured capacity — e.g. taken before a config bump); grown
            # back to the configured size after the load
            self.hnsw_cfg = self.hnsw_cfg._replace(capacity=cap)
            self.cfg = dataclasses.replace(self.cfg, capacity=cap)
            self.state = hnsw_init(self.hnsw_cfg)
            self._reset_containers(cap)
        tree = {"state": self.state, "batches": jnp.int32(0)}
        tree.update(self._extra_tree())
        got = ckpt.restore(ckpt_dir, step, tree)
        self.state = got["state"]
        self._batches = int(got["batches"])
        self._take_extra(got)
        if target > cap:
            self.grow(target)
        # re-derive ALL host-side deletion state from the restored device
        # arrays: tombstones and free-listed slots round-trip through the
        # checkpoint (they live in HNSWState), only the host mirrors need
        # rebuilding. Cumulative `deleted` is not persisted — it restarts
        # at the restored tombstone count.
        node_level = np.asarray(self.state.node_level)
        count = int(self.state.count)
        self._free = [int(i) for i in np.flatnonzero(node_level[:count] < 0)]
        self._n_dead = int(np.asarray(self.state.dead).sum())
        self._n_deleted = self._n_dead
        self._count_hw = count
        self._slots_q = []
        # re-anchor the overflow guard's sync-free bound on the restored
        # high-water mark (it must stay an UPPER bound of the true count)
        self._known_count = count
        self._dispatched_bound = 0
        return step


class HNSWBitmapBackend(_HNSWLifecycle):
    """FOLD's index: HNSW top-k over one-hot-folded bitmap signatures.

    Holds the HNSW state plus (optionally) the raw MinHash signatures of
    admitted docs for the beyond-paper exact-verify option
    (cfg.verify_minhash — rescores the k retrieved candidates with exact
    lane agreement inside `search`, removing the bitmap-threshold
    calibration approximation)."""

    name = "hnsw"
    order = BATCH_FIRST

    def __init__(self, cfg: FoldConfig):
        self.cfg = cfg
        self.hnsw_cfg = cfg.hnsw()
        self.state: HNSWState = hnsw_init(self.hnsw_cfg)
        self.tau_b = bitmap_tau(cfg)
        self._sig_store = (np.zeros((cfg.capacity, cfg.num_hashes), np.uint32)
                           if cfg.verify_minhash else None)
        self._batches = 0     # level-seed basis: monotone, sync-free

    # -- protocol: identity --------------------------------------------------
    @property
    def sig_spec(self) -> SigSpec:
        return SigSpec(num_hashes=self.cfg.num_hashes,
                       shingle_n=self.cfg.shingle_n, T=self.cfg.T,
                       seed=self.cfg.seed, use_kernel=self.cfg.use_kernel,
                       needs=frozenset({"sigs", "bitmaps"}))

    @property
    def tau_batch(self) -> float:
        return self.tau_b

    @property
    def tau_index(self) -> float:
        # exact-verify rescoring reports sims in MinHash space
        return self.cfg.tau if self.cfg.verify_minhash else self.tau_b

    @property
    def capacity(self) -> int:
        return self.hnsw_cfg.capacity

    # -- protocol: steps ② ③ ⑤ ----------------------------------------------
    def batch_sim(self, sig: SigBatch):
        cached = self.cfg.cached
        return ops.bitmap_jaccard(sig.bitmaps, sig.bitmaps,
                                  sig.pcs if cached else None,
                                  sig.pcs if cached else None,
                                  cached=cached, use_kernel=self.cfg.use_kernel)

    def search(self, sig: SigBatch):
        ids, sims = hnsw_search(self.hnsw_cfg, self.state, sig.bitmaps,
                                k=self.cfg.k)
        if self.cfg.verify_minhash:
            # rescore the k candidates with exact lane agreement (host
            # sync: reads ids + the numpy signature store)
            cand = self._sig_store[np.maximum(np.asarray(ids), 0)]  # foldlint: sync-ok(opt-in exact verify reads the host sig store)
            lane = (np.asarray(sig.sigs)[:, None, :] == cand).mean(-1)  # foldlint: sync-ok(opt-in exact verify reads the host sig store)
            sims = jnp.where(jnp.asarray(ids) >= 0,
                             jnp.asarray(lane, jnp.float32), -jnp.inf)
        return ids, sims

    def insert(self, sig: SigBatch, keep, search_ids=None):
        B = sig.bitmaps.shape[0]
        levels = self._sample_levels(B)
        # refuse BEFORE any state mutation: once past the guard, every keep
        # row is guaranteed a slot, so the sig-store scatter below stays in
        # lockstep with the device insert (no desync on partial inserts)
        free_dev, free_host = self._prepare_slots(keep, B)
        self._record_insert(sig, keep, free_host)
        self.state, _ = hnsw_insert_batch(self.hnsw_cfg, self.state,
                                          sig.bitmaps, sig.pcs, levels,
                                          jnp.asarray(keep),
                                          seed_ids=self._seeds_from(search_ids),
                                          free_slots=free_dev)
        return self.state.count     # timing handle (no sync implied)

    # -- lifecycle hooks (exact-verify signature store tracks capacity) ------
    def _after_grow(self, new_capacity: int) -> None:
        if self._sig_store is not None and len(self._sig_store) < new_capacity:
            pad = new_capacity - len(self._sig_store)
            self._sig_store = np.concatenate(
                [self._sig_store,
                 np.zeros((pad, self.cfg.num_hashes), np.uint32)])

    def _reset_containers(self, capacity: int) -> None:
        if self._sig_store is not None:
            self._sig_store = np.zeros((capacity, self.cfg.num_hashes),
                                       np.uint32)

    def _extra_tree(self) -> dict:
        if self._sig_store is None:
            return {}
        return {"sig_store": jnp.asarray(self._sig_store)}

    def _take_extra(self, got: dict) -> None:  # foldlint: cold-path (restore hook)
        if self._sig_store is not None:
            self._sig_store = np.asarray(got["sig_store"])

    # -- protocol: introspection ---------------------------------------------
    def stats_schema(self) -> tuple[str, ...]:
        return ("count", "capacity", "batches", "deleted", "dead", "free")

    def stats(self) -> dict:
        return {"count": self.inserted, "capacity": self.capacity,
                "batches": self._batches, "deleted": self._n_deleted,
                "dead": self._n_dead, "free": len(self._free or [])}


class RawHNSWBackend(_HNSWLifecycle):
    """FAISS (Jaccard) / FAISS (Hamming): identical index machinery to FOLD,
    but vertices are raw (H,) uint32 MinHash signatures scored by
      - minhash_jaccard: fraction of equal lanes (tie-heavy; low recall), or
      - hamming: bit agreement across the packed lanes (fast; misaligned).
    tau applies directly in the metric's own space."""

    name = "hnsw_raw"
    order = BATCH_FIRST

    def __init__(self, cfg: FoldConfig, metric: str = "minhash_jaccard"):
        assert metric in ("minhash_jaccard", "hamming"), metric
        self.cfg = cfg
        self.metric = metric
        self.hnsw_cfg = HNSWConfig(
            capacity=cfg.capacity, words=cfg.num_hashes, M=cfg.M, M0=cfg.M0,
            ef_construction=cfg.ef_construction, ef_search=cfg.ef_search,
            max_level=cfg.max_level, metric=metric,
            query_chunk=cfg.query_chunk,
            batched_insert=cfg.batched_insert)
        self.state: HNSWState = hnsw_init(self.hnsw_cfg)
        self._batches = 0     # level-seed basis: monotone, sync-free

    @property
    def sig_spec(self) -> SigSpec:
        return SigSpec(num_hashes=self.cfg.num_hashes,
                       shingle_n=self.cfg.shingle_n, seed=self.cfg.seed,
                       use_kernel=self.cfg.use_kernel,
                       needs=frozenset({"sigs"}))

    tau_batch = property(lambda self: self.cfg.tau)
    tau_index = property(lambda self: self.cfg.tau)

    @property
    def capacity(self) -> int:
        return self.hnsw_cfg.capacity

    def batch_sim(self, sig: SigBatch):
        from repro.core.bitmap import pairwise_hamming, pairwise_minhash_jaccard
        pair = (pairwise_minhash_jaccard if self.metric == "minhash_jaccard"
                else pairwise_hamming)
        return pair(sig.sigs, sig.sigs)

    def search(self, sig: SigBatch):
        return hnsw_search(self.hnsw_cfg, self.state, sig.sigs, k=self.cfg.k)

    def insert(self, sig: SigBatch, keep, search_ids=None):
        B = sig.sigs.shape[0]
        levels = self._sample_levels(B)
        free_dev, free_host = self._prepare_slots(keep, B)
        self._record_insert(sig, keep, free_host)
        pcs = jnp.zeros(B, jnp.int32)          # unused by raw metrics
        self.state, _ = hnsw_insert_batch(self.hnsw_cfg, self.state,
                                          sig.sigs, pcs, levels,
                                          jnp.asarray(keep),
                                          seed_ids=self._seeds_from(search_ids),
                                          free_slots=free_dev)
        return self.state.count     # timing handle (no sync implied)

    def stats_schema(self) -> tuple[str, ...]:
        return ("count", "capacity", "metric", "deleted", "dead", "free")

    def stats(self) -> dict:
        return {"count": self.inserted, "capacity": self.capacity,
                "metric": self.metric, "deleted": self._n_deleted,
                "dead": self._n_dead, "free": len(self._free or [])}


# -- analyzable program specs (repro.analysis / tools/foldprog) --------------
# Pinned spec geometry, deliberately independent of FoldConfig defaults so a
# default bump does not silently re-baseline the golden fingerprints: the
# gate measures THESE programs, the conformance tests measure behavior.
_SPEC_CAP = 8192      # index capacity (slots)
_SPEC_B = 128         # batch size (the service's largest default bucket)
_SPEC_K = 4
# every donated HNSWState leaf must survive into the lowered alias table
_STATE_LEAVES = len(HNSWState._fields)


def _spec_cfg(metric: str = "bitmap_jaccard") -> HNSWConfig:
    cfg = FoldConfig(capacity=_SPEC_CAP)
    hcfg = cfg.hnsw()
    if metric != "bitmap_jaccard":
        hcfg = hcfg._replace(metric=metric, words=cfg.num_hashes)
    return hcfg


def _search_spec(name: str, metric: str) -> ProgramSpec:
    def make():
        hcfg = _spec_cfg(metric)
        q = jax.ShapeDtypeStruct((_SPEC_B, hcfg.words), jnp.uint32)
        return hnsw_search, (hcfg, abstract_state(hcfg), q), {"k": _SPEC_K}
    return ProgramSpec(
        name=name, make=make, donate_expect=0,
        budget=ProgramBudget(temp_bytes=24_000_000, gather=220,
                             while_loops=8),
        tags=("roofline",))


def _insert_args(hcfg: HNSWConfig) -> tuple:
    sd = jax.ShapeDtypeStruct
    return (hcfg, abstract_state(hcfg),
            sd((_SPEC_B, hcfg.words), jnp.uint32),      # vecs
            sd((_SPEC_B,), jnp.int32),                  # pcs
            sd((_SPEC_B,), jnp.int32),                  # levels
            sd((_SPEC_B,), jnp.bool_),                  # keep mask
            sd((_SPEC_B, _SPEC_K), jnp.int32),          # seed_ids (reuse)
            sd((_SPEC_B,), jnp.int32))                  # free_slots


@register_programs("index.backends.hnsw")
def _hnsw_programs() -> list[ProgramSpec]:
    def make_insert():
        return hnsw_insert_batch, _insert_args(_spec_cfg()), {}

    def make_delete():
        hcfg = _spec_cfg()
        ids = jax.ShapeDtypeStruct((64,), jnp.int32)
        return hnsw_delete, (hcfg, abstract_state(hcfg), ids), {}

    def make_compact():
        hcfg = _spec_cfg()
        return hnsw_compact, (hcfg, abstract_state(hcfg)), {}

    return [
        _search_spec("hnsw/search", "bitmap_jaccard"),
        _search_spec("hnsw_raw/search", "minhash_jaccard"),
        ProgramSpec(
            name="hnsw/insert", make=make_insert,
            donate_expect=_STATE_LEAVES,
            budget=ProgramBudget(
                temp_bytes=64_000_000, scatter=200, while_loops=12,
                note="two-phase batched insert (discover + commit); the "
                     "donated state must alias every leaf or serving "
                     "doubles its index footprint"),
            tags=("roofline",)),
        ProgramSpec(
            name="hnsw/delete", make=make_delete,
            donate_expect=_STATE_LEAVES,
            budget=ProgramBudget(temp_bytes=8_000_000)),
        ProgramSpec(
            name="hnsw/compact", make=make_compact,
            donate_expect=_STATE_LEAVES - 2,
            budget=ProgramBudget(
                temp_bytes=800_000_000,
                note="adjacency repair scratch is capacity-quadratic-ish; "
                     "acceptable only because compact runs off the hot "
                     "path (lifecycle watermark). entry/top_level are "
                     "re-derived scalars, so only 6 of the 8 donated "
                     "leaves alias into outputs")),
    ]


@register("hnsw")
def _make_hnsw(cfg: FoldConfig | None = None, **opts) -> HNSWBitmapBackend:
    if opts:
        cfg = dataclasses.replace(cfg or FoldConfig(), **opts)
    return HNSWBitmapBackend(cfg or FoldConfig())


@register("hnsw_raw")
def _make_hnsw_raw(cfg: FoldConfig | None = None,
                   metric: str = "minhash_jaccard",
                   **opts) -> RawHNSWBackend:
    if opts:    # FoldConfig overrides (e.g. query_chunk), like "hnsw"
        cfg = dataclasses.replace(cfg or FoldConfig(), **opts)
    return RawHNSWBackend(cfg or FoldConfig(), metric=metric)
