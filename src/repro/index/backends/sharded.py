"""Mesh-sharded FOLD as a peer backend ("hnsw_sharded").

Each device along `axis` owns an independent HNSW sub-graph over 1/N of the
admitted corpus (capacity below is PER SHARD). The whole ②-⑤ step is one
lowered multi-device program (core/sharded.py), so this backend implements
the protocol's `fused_step` hook instead of split batch_sim/search/insert —
the generic DedupPipeline routes around the shared sweep when a backend
fuses. Batches are padded to a multiple of nshards (extra rows
valid=False), so the executor can drive this exactly like any other
backend. Retrieved neighbor ids/sims are internal to the sharded top-k
merge and surface as -1/-inf.

Full lifecycle peer of "hnsw" (growth, snapshots, deletion):

  * grow(new_total) re-pads every shard's state to ceil(new_total/nshards)
    per-shard slots (core.sharded.sharded_grow) and re-lowers the fused
    step, so the serving layer's sync-free occupancy watermark works
    multi-device.
  * save/restore writes ONE snapshot directory: the stacked per-shard
    state arrays (checkpoint gathers to host, so storage is device-count
    independent) plus a shard-layout manifest {"shards", "capacity"
    (per shard), "axis"}. A snapshot taken at N shards restores at N' >= N
    (scale-out: the N sub-graphs land on the first N shards, the rest
    start empty) and REFUSES N' < N — per-shard HNSW graphs cannot be
    merged. Scale-out restore invalidates previously exported global slot
    ids (the encoding below depends on nshards).
  * deletion routes by GLOBAL SLOT ID = local_slot * nshards + shard
    (round-robin interleaved — stable under grow(), which changes only the
    per-shard capacity): delete() splits ids by `id % nshards` and
    tombstones each shard's rows inside one shard_map program; compact()
    repairs/unlinks per sub-graph and re-derives per-shard host free
    lists; the fused step offers each shard its own reclaimed slots ahead
    of fresh capacity.

Search memory: the per-shard batched HNSW search inherits the memory-lean
defaults from core/hnsw.py — packed visited bitsets and capacity-derived
query chunking — via `FoldConfig.query_chunk` (cfg.hnsw() carries it into
the fused step's hnsw_search calls).

Insertion: the fused step uses the two-phase batched insert
(`FoldConfig.batched_insert`) per shard — phase-A discovery and phase-B
commit run on every sub-graph in parallel inside the shard_map program —
and seeds it with the ids the local sub-graph search just retrieved
(`FoldConfig.reuse_search`): one graph walk per document per shard, shared
between admission and ingest.
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
from repro.core.hnsw import abstract_state, hnsw_init, sample_levels
from repro.core.sharded import (make_sharded_compact, make_sharded_dedup_step,
                                make_sharded_delete, make_sharded_search,
                                sharded_grow, sharded_init,
                                sharded_state_specs)
from repro.index.protocol import (BATCH_FIRST, DedupBackend, SigBatch,
                                  SigSpec, StepResult)
from repro.index.registry import register

__all__ = ["ShardedDedupBackend"]


@jax.jit
def _live_count(node_level, dead):
    """All-shard admitted-minus-deleted occupancy as ONE cached device
    program (the eager form dispatched three ops per poll; the growth
    watermark polls this every batch)."""
    return jnp.sum((node_level >= 0) & ~dead, dtype=jnp.int32)


class ShardedDedupBackend(DedupBackend):
    name = "hnsw_sharded"
    order = BATCH_FIRST      # nominal; the fused step owns the ordering
    supports_growth = True
    supports_snapshots = True
    supports_deletion = True
    track_slots = False
    # host copy of the levels sampled for the latest step (the serving
    # executor pairs it with its batch for the commit_links histogram)
    last_levels: np.ndarray | None = None

    def __init__(self, cfg: FoldConfig, shards: int | None = None,
                 mesh=None, axis: str = "data"):
        if mesh is None:
            devices = jax.devices()
            n = len(devices) if shards is None else shards
            if n > len(devices):
                raise ValueError(
                    f"shards={n} but only {len(devices)} devices available")
            mesh = jax.sharding.Mesh(np.asarray(devices[:n]), (axis,))
        self.cfg = cfg
        self.mesh = mesh
        self.axis = axis
        self.nshards = mesh.shape[axis]
        self.hnsw_cfg = cfg.hnsw()
        self.states = sharded_init(self.hnsw_cfg, mesh, axis)
        self._lower()
        self._batches = 0
        # sync-free per-shard occupancy bound: round-robin keeps shards
        # within one doc of each other, so the max per-shard high-water
        # count plus a conservative per-batch charge upper-bounds them all
        self._known_max = 0
        self._bound = 0
        # -- deletion state (protocol DELETION CONTRACT) ---------------------
        self._n_deleted = 0        # cumulative successful deletes
        self._n_dead = 0           # live tombstones awaiting compact
        self._t_compact = 0.0
        self._free: list[list[int]] = [[] for _ in range(self.nshards)]
        self._count_hw: np.ndarray | None = None   # (nshards,) host mirror
        self._slots_q: list = []

    def _lower(self) -> None:
        """(Re-)lower the fused step + delete/compact programs against the
        current static per-shard capacity (called at init and after grow/
        restore — each pays one recompile on next use)."""
        self._step = jax.jit(make_sharded_dedup_step(
            self.hnsw_cfg, self.mesh, tau=bitmap_tau(self.cfg),
            k=self.cfg.k, axis=self.axis, masked=True,
            reuse_search=getattr(self.cfg, "reuse_search", True),
            free_slots=True))
        self._delete = jax.jit(make_sharded_delete(
            self.hnsw_cfg, self.mesh, axis=self.axis))
        self._compact = jax.jit(make_sharded_compact(
            self.hnsw_cfg, self.mesh, axis=self.axis))
        self._search = jax.jit(make_sharded_search(
            self.hnsw_cfg, self.mesh, k=self.cfg.k, axis=self.axis))

    @property
    def sig_spec(self) -> SigSpec:
        return SigSpec(num_hashes=self.cfg.num_hashes,
                       shingle_n=self.cfg.shingle_n, T=self.cfg.T,
                       seed=self.cfg.seed, use_kernel=self.cfg.use_kernel,
                       needs=frozenset({"sigs", "bitmaps"}))

    tau_batch = property(lambda self: bitmap_tau(self.cfg))
    tau_index = property(lambda self: bitmap_tau(self.cfg))

    @property
    def capacity(self) -> int:
        return self.hnsw_cfg.capacity * self.nshards

    @property
    def inserted(self) -> int:
        """LIVE document count across all shards (host sync)."""
        return int(_live_count(self.states.node_level,  # foldlint: sync-ok(occupancy poll; one fused cached program)
                               self.states.dead))

    # -- slot-id encoding ----------------------------------------------------
    # global slot id = local_slot * nshards + shard: stable under grow()
    # (which only changes the per-shard capacity, never nshards), dense in
    # [0, capacity), and decodable host-side without a device sync.
    def _decode_slots(self, ids: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        return ids % self.nshards, ids // self.nshards

    # -- overflow refusal ----------------------------------------------------
    def _guard_capacity(self, per_shard: int, offered_min: int) -> None:
        """Refuse a batch that could overflow ANY shard (sync-free bound).

        Round-robin assignment puts at most ceil(B/n) = per_shard docs on
        one shard; offered_min reclaimed slots are guaranteed available on
        every shard, so only the difference charges fresh capacity against
        the max per-shard high-water mark. Near capacity we pay one host
        sync for the true max, then either refuse with a grow() hint or
        re-anchor."""
        cap = self.hnsw_cfg.capacity
        fresh = max(0, per_shard - offered_min)
        if self._known_max + self._bound + fresh <= cap:
            self._bound += fresh
            return
        with TraceAnnotation("fold.sync.capacity"):
            self._known_max = int(jnp.max(self.states.count))  # foldlint: sync-ok(rare re-anchor: only when the sync-free bound says the batch might not fit)
        self._bound = 0
        if self._known_max + fresh > cap:
            raise RuntimeError(
                f"sharded index full: a shard holds {self._known_max} of "
                f"{cap} slots and the incoming batch may not fit; call "
                f"grow() — or compact() if tombstones are pending — (or "
                f"run under the service's IndexManager growth watermark) "
                f"before inserting — refusing to silently drop admitted "
                f"docs")
        self._bound = fresh

    # -- slot logging (track_slots / lifecycle ledger) -----------------------
    def _record_insert(self, keep, free_taken: list[list[int]]) -> None:
        """Host mirror of the fused step's per-shard slot assignment.

        Row r routes to shard r % nshards; within a shard, kept rows (in
        row order — hnsw_insert_batch's cumsum order) consume that shard's
        offered frees first, then fresh slots from its high-water count.
        Syncs `keep` — only called while track_slots is on. The count
        mirror is seeded from the PRE-insert device state in fused_step."""
        order = np.flatnonzero(np.asarray(keep))  # foldlint: sync-ok(slot logging is opt-in; lifecycle needs the host mask)
        taken = [0] * self.nshards
        slots = np.empty(len(order), np.int64)
        for j, r in enumerate(order):
            s = int(r) % self.nshards
            fh = free_taken[s]
            if taken[s] < len(fh):
                local = fh[taken[s]]
                taken[s] += 1
            else:
                local = int(self._count_hw[s])
                self._count_hw[s] += 1
            slots[j] = local * self.nshards + s
        self._slots_q.append(slots.astype(np.int32))

    # -- protocol: fused ②-⑤ -------------------------------------------------
    def fused_step(self, sig: SigBatch, valid=None) -> StepResult:
        bitmaps, pcs = sig.bitmaps, sig.pcs
        B = bitmaps.shape[0]
        pad = (-B) % self.nshards
        per_shard = (B + pad) // self.nshards
        # offer each shard up to per_shard reclaimed slots; the guard
        # credits only the count available on EVERY shard (conservative)
        offer = [f[:per_shard] for f in self._free]
        self._guard_capacity(per_shard, min(len(o) for o in offer))
        self._free = [f[len(o):] for f, o in zip(self._free, offer)]
        frees = np.full((self.nshards, per_shard), -1, np.int32)
        for s, o in enumerate(offer):
            frees[s, :len(o)] = o
        if valid is None:
            valid = np.ones((B,), bool)
        if pad:
            bitmaps = jnp.pad(bitmaps, ((0, pad), (0, 0)))
            pcs = jnp.pad(pcs, (0, pad))
            valid = np.pad(np.asarray(valid), (0, pad))  # foldlint: sync-ok(valid is host numpy by contract; pad before device upload)
        lv = sample_levels(B + pad, self.hnsw_cfg,
                           seed=self._batches + self.cfg.seed + 1)
        self.last_levels = lv[:B]
        levels = jnp.asarray(lv)
        self._batches += 1
        if self.track_slots and self._count_hw is None:
            # one-time sync of the per-shard high-water mirror, BEFORE the
            # step so this batch's own inserts are not double-counted
            self._count_hw = np.asarray(self.states.count).copy()  # foldlint: sync-ok(one-time count-mirror seed; advanced host-side after)
        self.states, keep, keep_in = self._step(
            self.states, bitmaps, pcs, levels, jnp.asarray(valid),
            jnp.asarray(frees))
        if self.track_slots:
            self._record_insert(keep, offer)
        else:
            self._count_hw = None    # host count mirror goes stale
        # the merged top-k per query is internal to the sharded program;
        # surface the verdict with neighbor ids unknown (-1)
        k = self.cfg.k
        ids = jnp.full((B, k), -1, jnp.int32)
        sims = jnp.full((B, k), -jnp.inf, jnp.float32)
        return StepResult(keep=keep[:B], keep_in_batch=keep_in[:B],
                          ids=ids, sims=sims)

    # unreached on the admission path while fused_step exists, but `search`
    # also serves the READ-ONLY query path (DedupPipeline.query — the
    # cluster replicas): merged global top-k with interleaved global ids.
    def search(self, sig: SigBatch):
        bitmaps, pcs = sig.bitmaps, sig.pcs
        B = bitmaps.shape[0]
        pad = (-B) % self.nshards
        if pad:
            bitmaps = jnp.pad(bitmaps, ((0, pad), (0, 0)))
            pcs = jnp.pad(pcs, (0, pad))
        ids, sims = self._search(self.states, bitmaps, pcs)
        return ids[:B], sims[:B]

    def batch_sim(self, sig):
        raise NotImplementedError("fused backend: use fused_step")

    def insert(self, sig, keep):
        raise NotImplementedError("fused backend: use fused_step")

    # -- deletion / compaction (protocol DELETION CONTRACT) ------------------
    @property
    def deleted(self) -> int:
        return self._n_deleted

    @property
    def dead_fraction(self) -> float:
        # host-exact tombstone counter: no device sync (polled every batch)
        return self._n_dead / max(self.capacity, 1)

    def delete(self, ids) -> int:  # foldlint: cold-path
        """Tombstone global slot ids, each routed to its owning shard
        (id % nshards) and tombstoned locally inside one shard_map program.
        Idempotent; slots become reusable only after compact()."""
        ids = np.unique(np.asarray(ids, np.int64).reshape(-1))
        ids = ids[(ids >= 0) & (ids < self.capacity)]
        if len(ids) == 0:
            return 0
        shard, local = self._decode_slots(ids)
        per = [local[shard == s] for s in range(self.nshards)]
        width = max(len(p) for p in per)
        # pad to the next power of two for stable compiled shapes
        D = 1 << int(width - 1).bit_length() if width > 1 else 1
        mat = np.full((self.nshards, D), -1, np.int64)
        for s, p in enumerate(per):
            mat[s, :len(p)] = p
        self.states, n_dev = self._delete(self.states,
                                          jnp.asarray(mat, jnp.int32))
        n = int(np.asarray(n_dev).sum())        # host sync
        self._n_deleted += n
        self._n_dead += n
        return n

    def compact(self) -> dict:  # foldlint: cold-path
        """Repair every sub-graph's adjacency around its tombstones, unlink
        them, and re-derive the per-shard host free lists from the device
        state (host sync — callers schedule this off the hot path)."""
        t0 = time.perf_counter()
        self.states, n_dev = self._compact(self.states)
        reclaimed = int(np.asarray(n_dev).sum())
        node_level = np.asarray(self.states.node_level)     # (n, cap)
        counts = np.asarray(self.states.count)              # (n,)
        self._free = [
            [int(i) for i in np.flatnonzero(node_level[s, :counts[s]] < 0)]
            for s in range(self.nshards)]
        self._n_dead = 0
        self._count_hw = counts.copy()
        self._known_max = int(counts.max())     # re-anchor overflow guard
        self._bound = 0
        self._t_compact += time.perf_counter() - t0
        return {"reclaimed": reclaimed,
                "free": sum(len(f) for f in self._free),
                "t_compact": self._t_compact}

    # -- lifecycle -----------------------------------------------------------
    def grow(self, new_capacity: int) -> None:  # foldlint: cold-path
        """Re-pad every shard to ceil(new_capacity/nshards) per-shard slots
        (graphs preserved exactly) and re-lower the fused step.

        new_capacity is TOTAL capacity, matching the `capacity` property —
        the serving watermark computes its geometric target from the total.
        Global slot ids are interleaved (local*nshards+shard), so ids
        exported before a grow stay valid after it."""
        per_shard = -(-new_capacity // self.nshards)
        if per_shard <= self.hnsw_cfg.capacity:
            return
        self.hnsw_cfg, self.states = sharded_grow(
            self.hnsw_cfg, self.states, per_shard, self.mesh, self.axis)
        self.cfg = dataclasses.replace(self.cfg, capacity=per_shard)
        self._lower()
        # growth already pays a recompile; re-derive the sync-free bound
        self._known_max = int(jnp.max(self.states.count))
        self._bound = 0

    def save(self, ckpt_dir: str, step: int, async_write: bool = False):  # foldlint: cold-path
        """One coordinated snapshot: the stacked per-shard HNSW arrays
        (gathered to host by the checkpoint layer — storage is device-count
        independent) plus the shard-layout manifest."""
        from repro.train import checkpoint as ckpt
        tree = {"states": self.states, "batches": jnp.int32(self._batches)}
        writer = ckpt.save_async if async_write else ckpt.save
        writer(ckpt_dir, step, tree,
               extra={"capacity": self.hnsw_cfg.capacity,
                      "shards": self.nshards, "axis": self.axis})

    def restore(self, ckpt_dir: str, step: int | None = None) -> int:  # foldlint: cold-path
        """Restore a coordinated snapshot onto this backend's mesh.

        Shard-layout rules: a snapshot taken at N shards restores exactly
        at N' == N; N' > N is a scale-out restore (the N saved sub-graphs
        land on the first N shards, the rest start empty — admission
        round-robins over all N'); N' < N is REFUSED (per-shard HNSW
        graphs cannot be merged). Per-shard capacity mismatches follow the
        "hnsw" convention: the snapshot's capacity is adopted, then grown
        back up to the configured size if smaller."""
        from repro.train import checkpoint as ckpt
        step = ckpt.latest_step(ckpt_dir) if step is None else step
        if step is None:     # a bare assert would vanish under python -O
            raise FileNotFoundError(
                f"no committed checkpoint found in {ckpt_dir!r}")
        meta = ckpt.manifest(ckpt_dir, step)
        snap_shards = int(meta.get("shards", 1))
        if snap_shards > self.nshards:
            raise ValueError(
                f"snapshot was taken at {snap_shards} shards but this "
                f"backend has {self.nshards}: per-shard HNSW graphs cannot "
                f"be merged — restore on >= {snap_shards} shards (scale-out "
                f"is supported, scale-in is not)")
        snap_cap = int(meta.get("capacity", self.hnsw_cfg.capacity))
        # host-side like-tree at the SNAPSHOT geometry (restore only checks
        # pytree structure; leaf shapes come from the saved arrays)
        one = hnsw_init(self.hnsw_cfg._replace(capacity=snap_cap))
        like = {"states": jax.tree.map(
                    lambda x: jnp.broadcast_to(x, (snap_shards,) + x.shape),
                    one),
                "batches": jnp.int32(0)}
        got = ckpt.restore(ckpt_dir, step, like, device=False)
        st = got["states"]
        exp = (snap_shards, snap_cap, self.hnsw_cfg.words)
        if tuple(st.vectors.shape) != exp:
            raise ValueError(
                f"snapshot geometry {tuple(st.vectors.shape)} does not "
                f"match manifest/config expectation {exp} "
                f"(words/M0/max_level must match the saving config)")
        # assemble the target-geometry stacked arrays: pad empty shards
        # (scale-out) and empty per-shard slots (capacity adopt-then-grow)
        cap_t = max(snap_cap, self.hnsw_cfg.capacity)
        n, sn = self.nshards, snap_shards
        pad_n, pad_c = n - sn, cap_t - snap_cap
        def padded(a, cval, cap_axis):
            width = [(0, 0)] * a.ndim
            width[0] = (0, pad_n)
            if cap_axis is not None:
                width[cap_axis] = (0, pad_c)
            return np.pad(a, width, constant_values=cval)
        stacked = type(st)(
            vectors=padded(st.vectors, 0, 1),
            pb=padded(st.pb, 0, 1),
            neighbors=padded(st.neighbors, -1, 2),
            node_level=padded(st.node_level, -1, 1),
            dead=padded(st.dead, False, 1),
            entry=padded(st.entry, -1, None),
            top_level=padded(st.top_level, -1, None),
            count=padded(st.count, 0, None),
        )
        self.hnsw_cfg = self.hnsw_cfg._replace(capacity=cap_t)
        self.cfg = dataclasses.replace(self.cfg, capacity=cap_t)
        specs = sharded_state_specs(self.mesh, self.axis)
        self.states = jax.tree.map(
            lambda x, s: jax.device_put(jnp.asarray(x), s), stacked, specs)
        self._lower()
        self._batches = int(got["batches"])
        # re-derive ALL host-side deletion state from the restored arrays:
        # tombstones and free-listed slots round-trip through the snapshot
        # (they live in the stacked HNSWState), only host mirrors rebuild.
        node_level = np.asarray(self.states.node_level)
        counts = np.asarray(self.states.count)
        self._free = [
            [int(i) for i in np.flatnonzero(node_level[s, :counts[s]] < 0)]
            for s in range(self.nshards)]
        self._n_dead = int(np.asarray(self.states.dead).sum())
        self._n_deleted = self._n_dead
        self._count_hw = counts.copy()
        self._slots_q = []
        self._known_max = int(counts.max())
        self._bound = 0
        return step

    def stats_schema(self) -> tuple[str, ...]:
        return ("count", "capacity", "shards", "deleted", "dead", "free")

    def stats(self) -> dict:
        return {"count": self.inserted, "capacity": self.capacity,
                "shards": self.nshards, "deleted": self._n_deleted,
                "dead": self._n_dead,
                "free": sum(len(f) for f in self._free)}


# -- analyzable program specs (repro.analysis / tools/foldprog) --------------
# The fused ②-⑤ step on a PINNED single-device mesh: shard_map lowering is
# per-shard, so one shard is enough to fingerprint the program the real mesh
# replicates — and it keeps the golden independent of the host's device
# count (the CI programs lane runs on one CPU device).
_SPEC_CAP = 4096      # per-shard capacity (smaller than hnsw/: the fused
_SPEC_B = 64          # step is the slowest compile in the gate)


@register_programs("index.backends.sharded")
def _sharded_programs() -> list[ProgramSpec]:
    def make_step():
        cfg = FoldConfig(capacity=_SPEC_CAP)
        hcfg = cfg.hnsw()
        mesh = jax.sharding.Mesh(
            np.asarray(  # foldlint: sync-ok(trace-time mesh construction)
                jax.devices()[:1]), ("data",))
        step = jax.jit(make_sharded_dedup_step(
            hcfg, mesh, tau=bitmap_tau(cfg), k=cfg.k, axis="data",
            masked=True, reuse_search=True, free_slots=True))
        one = abstract_state(hcfg)
        states = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((1,) + s.shape, s.dtype), one)
        sd = jax.ShapeDtypeStruct
        return step, (states,
                      sd((_SPEC_B, hcfg.words), jnp.uint32),   # bitmaps
                      sd((_SPEC_B,), jnp.int32),               # pcs
                      sd((_SPEC_B,), jnp.int32),               # levels
                      sd((_SPEC_B,), jnp.bool_),               # valid
                      sd((1, _SPEC_B), jnp.int32)), {}         # frees
    return [ProgramSpec(
        name="hnsw_sharded/fused_step", make=make_step,
        donate_expect=0,
        budget=ProgramBudget(
            temp_bytes=900_000_000,
            note="donation deliberately OFF: measured on the CPU dry-run "
                 "backend, donating the sharded caches RAISED temp bytes "
                 "(no aliasing model); revisit when lowering for a real "
                 "accelerator mesh"),
        tags=("roofline",))]


@register("hnsw_sharded")
def _make_sharded(cfg: FoldConfig | None = None, shards: int | None = None,
                  mesh=None, axis: str = "data", **opts):
    if opts:    # FoldConfig overrides (e.g. query_chunk), like "hnsw"
        cfg = dataclasses.replace(cfg or FoldConfig(), **opts)
    return ShardedDedupBackend(cfg or FoldConfig(), shards=shards, mesh=mesh,
                               axis=axis)
