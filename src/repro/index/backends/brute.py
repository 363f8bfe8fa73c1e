"""Brute-force online admission — the exact reference (Table 1 ground truth).

Per incoming document: exact MinHash-Jaccard against *every* admitted
signature (chunked through the Pallas-backed pairwise kernel on the raw
lanes). O(N) per doc — the 5-day column of Table 1, and the reference
labeler for recall (the paper validates DPK as equivalent to it).

Deletion is eager (no tombstones): a deleted row is masked out of every
subsequent search and its slot goes straight onto a free list that insert
drains before consuming fresh rows — so `dead_fraction` stays 0.0 and
`compact()` is the protocol no-op.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.programs import (ProgramBudget, ProgramSpec,
                                     register_programs)
from repro.core.bitmap import pairwise_minhash_jaccard
from repro.core.dedup import FoldConfig
from repro.index.protocol import BATCH_FIRST, DedupBackend, SigBatch, SigSpec
from repro.index.registry import register

__all__ = ["BruteForceBackend"]

_CHUNK = 8192      # db-axis chunking bounds the (B, N) similarity temp


@jax.jit
def _chunk_best(qsigs, db_chunk, free_mask):
    """Similarity + free-mask + per-query best for one db chunk, as ONE
    device program: only two (B,) vectors ever cross back to host. The
    mask is applied unconditionally so the masked/unmasked cases share a
    single compiled program (free rows score -inf and never win)."""
    sim = pairwise_minhash_jaccard(qsigs, db_chunk)
    sim = jnp.where(free_mask[None, :], -jnp.inf, sim)
    return jnp.argmax(sim, axis=1).astype(jnp.int32), jnp.max(sim, axis=1)


class BruteForceBackend(DedupBackend):
    name = "brute"
    order = BATCH_FIRST
    supports_growth = True
    supports_snapshots = True
    supports_deletion = True
    track_slots = False

    def __init__(self, cfg: FoldConfig):
        self.cfg = cfg
        self.store = np.zeros((cfg.capacity, cfg.num_hashes), np.uint32)
        self.n = 0                       # high-water row mark
        self._free: list[int] = []       # deleted rows < n, reusable
        self._free_mask = np.zeros(cfg.capacity, bool)
        self._n_deleted = 0

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
        return len(self.store)

    @property
    def inserted(self) -> int:
        return self.n - len(self._free)

    @property
    def deleted(self) -> int:
        return self._n_deleted

    def batch_sim(self, sig: SigBatch):
        return pairwise_minhash_jaccard(sig.sigs, sig.sigs)

    def search(self, sig: SigBatch):
        B = sig.sigs.shape[0]
        ids = np.full((B, 1), -1, np.int32)
        sims = np.full((B, 1), -np.inf, np.float32)
        if self.n > 0:
            # whole chunks only (rows past n score as free), so the scan
            # compiles once instead of once per distinct occupancy
            n_pad = min(-(-self.n // _CHUNK) * _CHUNK, self.capacity)
            db = jnp.asarray(self.store[:n_pad])
            for s in range(0, self.n, _CHUNK):
                e = min(s + _CHUNK, n_pad)
                fm = self._free_mask[s:e] | (np.arange(s, e) >= self.n)
                j_dev, best_dev = _chunk_best(sig.sigs, db[s:e],
                                              jnp.asarray(fm))
                # the per-chunk running max lives on host; two (B,)
                # vectors is the whole transfer
                j = np.asarray(j_dev)        # foldlint: sync-ok(chunk-reduction materialization point)
                best = np.asarray(best_dev)  # foldlint: sync-ok(chunk-reduction materialization point)
                better = best > sims[:, 0]
                ids[better, 0] = (s + j[better]).astype(np.int32)
                sims[better, 0] = best[better]
        return ids, sims

    def insert(self, sig: SigBatch, keep, search_ids=None) -> None:
        # the store is host numpy by design (the exact baseline is
        # O(N)-bound on similarity, not on this copy)
        new = np.asarray(sig.sigs)[np.asarray(keep)]  # foldlint: sync-ok(host store ingest)
        t = min(len(new), len(self._free))
        fresh = len(new) - t
        if self.n + fresh > self.capacity:
            raise RuntimeError(
                f"brute store full: {self.n} of {self.capacity} rows used "
                f"and the batch admits {fresh} beyond the free list; call "
                f"grow() — refusing to silently drop admitted docs")
        slots = np.concatenate(
            [np.asarray(self._free[:t], np.int64),  # foldlint: sync-ok(host free-list bookkeeping)
             self.n + np.arange(fresh, dtype=np.int64)]).astype(np.int32)
        self._free = self._free[t:]
        self.store[slots] = new
        self._free_mask[slots] = False
        self.n += fresh
        if self.track_slots:
            q = list(getattr(self, "_slots_q", []))
            q.append(slots)
            self._slots_q = q

    def delete(self, ids) -> int:  # foldlint: cold-path
        ids = np.unique(np.asarray(ids, np.int64).reshape(-1))
        ids = ids[(ids >= 0) & (ids < self.n)]
        ids = ids[~self._free_mask[ids]]
        if len(ids) == 0:
            return 0
        self._free_mask[ids] = True
        self._free = sorted(self._free + [int(i) for i in ids])
        self._n_deleted += len(ids)
        return len(ids)

    def grow(self, new_capacity: int) -> None:  # foldlint: cold-path
        if new_capacity <= self.capacity:
            return
        pad = new_capacity - self.capacity
        self.store = np.concatenate(
            [self.store, np.zeros((pad, self.cfg.num_hashes), np.uint32)])
        self._free_mask = np.concatenate(
            [self._free_mask, np.zeros(pad, bool)])

    def save(self, ckpt_dir: str, step: int, async_write: bool = False):  # foldlint: cold-path
        from repro.train import checkpoint as ckpt
        writer = ckpt.save_async if async_write else ckpt.save
        writer(ckpt_dir, step,
               {"store": self.store, "n": np.int64(self.n),
                "free_mask": self._free_mask.astype(np.uint8)},
               extra={"capacity": self.capacity})

    def restore(self, ckpt_dir: str, step: int | None = None) -> int:  # foldlint: cold-path
        from repro.train import checkpoint as ckpt
        step = ckpt.latest_step(ckpt_dir) if step is None else step
        if step is None:     # a bare assert would vanish under python -O
            raise FileNotFoundError(
                f"no committed checkpoint found in {ckpt_dir!r}")
        meta = ckpt.manifest(ckpt_dir, step)
        cap = int(meta.get("capacity", self.capacity))
        target = max(cap, self.capacity)
        tmpl = {"store": np.zeros((cap, self.cfg.num_hashes), np.uint32),
                "n": np.int64(0), "free_mask": np.zeros(cap, np.uint8)}
        got = ckpt.restore(ckpt_dir, step, tmpl, device=False)
        self.store, self.n = got["store"], int(got["n"])
        self._free_mask = np.asarray(got["free_mask"], bool)
        # the free list round-trips through the mask; cumulative `deleted`
        # is not persisted and restarts at the restored free count
        self._free = [int(i) for i in np.flatnonzero(self._free_mask[:self.n])]
        self._n_deleted = len(self._free)
        self._slots_q = []
        if target > cap:
            self.grow(target)
        return step

    def stats_schema(self) -> tuple[str, ...]:
        return ("count", "capacity", "deleted", "free")

    def stats(self) -> dict:
        return {"count": self.inserted, "capacity": self.capacity,
                "deleted": self._n_deleted, "free": len(self._free)}


# -- analyzable program specs (repro.analysis / tools/foldprog) --------------
@register_programs("index.backends.brute")
def _brute_programs() -> list[ProgramSpec]:
    def make_chunk():
        sd = jax.ShapeDtypeStruct
        H = FoldConfig().num_hashes
        return _chunk_best, (sd((128, H), jnp.uint32),
                             sd((_CHUNK, H), jnp.uint32),
                             sd((_CHUNK,), jnp.bool_)), {}
    return [ProgramSpec(
        name="brute/chunk_best", make=make_chunk,
        donate_expect=0,
        budget=ProgramBudget(
            temp_bytes=600_000_000, while_loops=0,
            note="the (B, CHUNK) similarity temp IS the baseline's cost "
                 "model — _CHUNK bounds it by construction"))]


@register("brute")
def _make_brute(cfg: FoldConfig | None = None):
    return BruteForceBackend(cfg or FoldConfig())
