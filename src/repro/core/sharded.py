"""Distributed FOLD: index-sharded dedup via shard_map (the 1000+-node path).

The paper runs FOLD on one big-memory VM. To scale corpus construction to a
pod (and beyond), FOLD-TPU shards the HNSW index across the mesh `data`
axis: every device owns an independent HNSW sub-graph over 1/N of the
admitted corpus. Per incoming batch:

  1. each host contributes its local query shard; queries are all-gathered
     (signatures are tiny: 512 B/doc — gathering 100K docs is 51 MB);
  2. every device searches its local sub-graph for ALL queries (bounded
     beam, local compute — this is where the paper's bitmap kernel runs);
  3. per-query top-k results are merged across shards with an all-gather +
     top-k (k and nshards are small, the merge is negligible);
  4. documents that survive the threshold are assigned to a shard by
     round-robin over their batch index and inserted locally.

Recall property: searching N sub-graphs of size C/N and merging top-k is
*at least* as accurate as one size-C graph search with the same ef (each
sub-search explores ef nodes of a smaller graph), so distribution does not
trade recall — it adds it. Throughput: per-device search cost drops with
corpus shard size; query fan-out is the cost, hidden by batching.

Used by launch/dryrun.py as the paper-technique dry-run cell, lowering the
whole step (gather -> HNSW while_loops -> merge -> insert) on the 16x16 and
2x16x16 meshes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.hnsw import (HNSWConfig, HNSWState, hnsw_compact, hnsw_delete,
                             hnsw_init, hnsw_insert_batch, hnsw_search)
from repro.index.pipeline import greedy_leader
from repro.kernels import ref as kref

__all__ = ["sharded_init", "sharded_init_program", "make_sharded_dedup_step",
           "sharded_state_specs", "sharded_grow", "make_sharded_delete",
           "make_sharded_compact", "make_sharded_search"]


def sharded_init(cfg: HNSWConfig, mesh: Mesh, axis: str = "data") -> HNSWState:
    """Stacked per-shard states with a leading device axis (sharded)."""
    return sharded_init_program(cfg, mesh, axis)()


def sharded_init_program(cfg: HNSWConfig, mesh: Mesh, axis: str = "data"):
    """The argument-free program `sharded_init` runs.

    It is sharded on its output, so each device fills only its own shard:
    the stacked state (N x one chip's index) never has to fit on a single
    device."""
    n = mesh.shape[axis]
    return jax.jit(
        lambda: jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape),
                             hnsw_init(cfg)),
        out_shardings=sharded_state_specs(mesh, axis))


def sharded_state_specs(mesh: Mesh, axis: str = "data"):
    """NamedShardings for the stacked HNSWState."""
    def spec(x=None):
        return NamedSharding(mesh, P(axis))
    return HNSWState(*(spec() for _ in HNSWState._fields))


def sharded_grow(cfg: HNSWConfig, states: HNSWState, new_capacity: int,
                 mesh: Mesh, axis: str = "data"
                 ) -> tuple[HNSWConfig, HNSWState]:
    """Re-pad every shard's state to a larger PER-SHARD capacity.

    The stacked-state analogue of core.hnsw.hnsw_grow: each sub-graph is
    preserved exactly (new slots empty, -1 level / -1 adjacency), the
    per-shard scalars (entry/top_level/count) are untouched, and the result
    is re-placed onto the mesh with the same leading-axis shardings. The
    caller re-lowers the fused step against the new static capacity (one
    recompile per growth — the serving layer grows geometrically)."""
    if new_capacity < cfg.capacity:
        raise ValueError(f"cannot shrink: {new_capacity} < {cfg.capacity}")
    if new_capacity == cfg.capacity:
        return cfg, states
    pad = new_capacity - cfg.capacity
    new_cfg = cfg._replace(capacity=new_capacity)
    new_states = HNSWState(
        vectors=jnp.pad(states.vectors, ((0, 0), (0, pad), (0, 0))),
        pb=jnp.pad(states.pb, ((0, 0), (0, pad))),
        neighbors=jnp.pad(states.neighbors, ((0, 0), (0, 0), (0, pad),
                                             (0, 0)), constant_values=-1),
        node_level=jnp.pad(states.node_level, ((0, 0), (0, pad)),
                           constant_values=-1),
        dead=jnp.pad(states.dead, ((0, 0), (0, pad))),
        entry=states.entry,
        top_level=states.top_level,
        count=states.count,
    )
    specs = sharded_state_specs(mesh, axis)
    return new_cfg, jax.tree.map(lambda x, s: jax.device_put(x, s),
                                 new_states, specs)


def _smap(mesh: Mesh):
    """shard_map over `mesh` with the replication check off."""
    return functools.partial(jax.shard_map, mesh=mesh, check_vma=False)


def _state_specs_p(axis: str):
    return HNSWState(*(P(axis),) * len(HNSWState._fields))


def make_sharded_delete(cfg: HNSWConfig, mesh: Mesh, axis: str = "data"):
    """Returns jit-able `delete(states, ids) -> (states, n_newly_dead)`.

    ids (nshards, D) int32, -1 padded, LOCAL per-shard slot ids sharded on
    the leading axis — each device tombstones its own rows (core.hnsw
    hnsw_delete semantics: out-of-range / unused / already-dead ignored).
    n_newly_dead comes back per shard, (nshards,)."""
    def local(state, ids):
        state = jax.tree.map(lambda x: x[0], state)
        state, n = hnsw_delete(cfg, state, ids[0])
        return jax.tree.map(lambda x: x[None], state), n[None]

    return _smap(mesh)(local, in_specs=(_state_specs_p(axis), P(axis)),
                       out_specs=(_state_specs_p(axis), P(axis)))


def make_sharded_search(cfg: HNSWConfig, mesh: Mesh, *, k: int = 4,
                        axis: str = "data", query_chunk: int | None = None):
    """Returns jit-able read-only `search(states, bitmaps, pcs) ->
    (ids, sims)`: every shard searches its sub-graph for the all-gathered
    queries, the per-shard top-k are merged into one global top-k, and ids
    come back as GLOBAL interleaved slot ids (local * nshards + shard) —
    the replica/query serving path of the sharded backend. bitmaps/pcs are
    sharded over `axis` on the batch dim; outputs (B, k) replicated."""
    nshards = mesh.shape[axis]

    def local(state, bitmaps, pcs):
        state = jax.tree.map(lambda x: x[0], state)
        my = jax.lax.axis_index(axis)
        q = jax.lax.all_gather(bitmaps, axis, tiled=True)       # (B, W)
        pc = jax.lax.all_gather(pcs, axis, tiled=True)
        ids, sims = hnsw_search(cfg, state, q, k=k, query_chunk=query_chunk)
        gids = jnp.where(ids >= 0, ids * nshards + my, -1)
        sims = jnp.where(ids >= 0, sims, -jnp.inf)
        all_ids = jax.lax.all_gather(gids, axis)                # (n, B, k)
        all_sims = jax.lax.all_gather(sims, axis)
        B = q.shape[0]
        all_ids = jnp.moveaxis(all_ids, 0, 1).reshape(B, -1)    # (B, n*k)
        all_sims = jnp.moveaxis(all_sims, 0, 1).reshape(B, -1)
        top, ix = jax.lax.top_k(all_sims, k)
        mids = jnp.take_along_axis(all_ids, ix, axis=1)
        return jnp.where(jnp.isfinite(top), mids, -1), top

    return _smap(mesh)(local,
                       in_specs=(_state_specs_p(axis), P(axis), P(axis)),
                       out_specs=(P(), P()))


def make_sharded_compact(cfg: HNSWConfig, mesh: Mesh, axis: str = "data"):
    """Returns jit-able `compact(states) -> (states, n_reclaimed)`.

    Runs core.hnsw's online compaction (adjacency repair around tombstones,
    unlink, entry re-election) independently on every sub-graph; shards
    never reference each other's slots so per-shard repair is complete.
    n_reclaimed comes back per shard, (nshards,)."""
    def local(state):
        state = jax.tree.map(lambda x: x[0], state)
        state, n = hnsw_compact(cfg, state)
        return jax.tree.map(lambda x: x[None], state), n[None]

    return _smap(mesh)(local, in_specs=(_state_specs_p(axis),),
                       out_specs=(_state_specs_p(axis), P(axis)))


def make_sharded_dedup_step(cfg: HNSWConfig, mesh: Mesh, *, tau: float,
                            k: int = 4, axis: str = "data",
                            query_chunk: int | None = None,
                            sub_batches: int = 1,
                            masked: bool = False,
                            reuse_search: bool = True,
                            free_slots: bool = False):
    """Returns jit-able `step(states, bitmaps, pcs, levels) -> (states, keep)`.

    bitmaps (B, W) sharded over `axis` on the batch dim; states stacked
    (nshards, ...) sharded on the leading dim. keep (B,) replicated.

    sub_batches > 1 splits the gathered batch into sequential slices (the
    paper's Fig. 9 protocol: 100K streaming docs processed in 10K batches):
    slice j is deduped against the index that already contains slices < j,
    bounding the quadratic in-batch work and the search working set.
    query_chunk bounds the (chunk, visited-words) working set of the batched
    HNSW search; None defers to hnsw_search's resolution (cfg.query_chunk,
    else a capacity-derived default), 0 disables chunking.

    masked=True adds a 5th argument `valid (B,) bool` (sharded like the
    batch): False rows are shape padding from the serving micro-batcher —
    they are excluded from admission and their keep comes back False. The
    step then returns (states, keep, keep_in) so the serving layer can
    distinguish in-batch duplicates from index duplicates.

    reuse_search=True seeds the local sub-graph's batched insert with the
    ids the step-(3) local search just retrieved for the same queries —
    the fused step never walks its shard twice for one document. Only
    consulted when cfg.batched_insert is on.

    free_slots=True adds a trailing argument `frees (nshards, F) int32`
    (-1 padded, sharded on the leading axis like the states): each shard's
    row holds reclaimed LOCAL slot ids (from make_sharded_compact) that its
    insert consumes before fresh capacity — the deletion contract's
    free-slot reuse, per shard. Incompatible with sub_batches > 1 (each
    sub-batch would re-consume the same frees).
    """
    nshards = mesh.shape[axis]
    if free_slots and sub_batches > 1:
        raise ValueError("free_slots is incompatible with sub_batches > 1")

    def one_sub(state, my, q, pc, lv, va, fs=None):
        B = q.shape[0]
        # (2) in-batch dedup — block-chunked pairwise (no (B,B,W) temp)
        from repro.core.bitmap import chunked_pairwise_bitmap_jaccard
        sim_in = chunked_pairwise_bitmap_jaccard(q, q, pc, pc)
        keep_in = greedy_leader(sim_in, tau)
        # (3) local sub-graph search for all queries
        ids, sims = hnsw_search(cfg, state, q, k=k, query_chunk=query_chunk)
        # (4) merge top-k across shards: max similarity is all we need
        best = jnp.max(jnp.where(ids >= 0, sims, -jnp.inf), axis=-1)
        best_global = jax.lax.pmax(best, axis)
        keep = keep_in & (best_global < tau)
        if va is not None:
            keep = keep & va
        # (5) round-robin shard assignment for admitted docs; the local
        # search above already holds each query's local neighborhood, so
        # the batched insert is seeded with it instead of re-descending
        mine = (jnp.arange(B, dtype=jnp.int32) % nshards) == my
        seeds = ids if (reuse_search and cfg.batched_insert) else None
        state, _ = hnsw_insert_batch(cfg, state, q, pc, lv, keep & mine,
                                     seed_ids=seeds, free_slots=fs)
        return state, keep, keep_in

    def local(state, bitmaps, pcs, levels, *rest):
        valid = rest[0] if masked else None
        frees = rest[-1] if free_slots else None
        # shard_map keeps a size-1 leading block axis; drop it per device
        state = jax.tree.map(lambda x: x[0], state)
        my = jax.lax.axis_index(axis)
        # (1) gather the full query batch (signatures are small)
        q_all = jax.lax.all_gather(bitmaps, axis, tiled=True)   # (B, W)
        pc_all = jax.lax.all_gather(pcs, axis, tiled=True)
        lv_all = jax.lax.all_gather(levels, axis, tiled=True)
        va_all = (jax.lax.all_gather(valid, axis, tiled=True)
                  if valid is not None else None)
        B = q_all.shape[0]
        if sub_batches > 1 and B % sub_batches == 0:
            sb = B // sub_batches
            keeps, keep_ins = [], []
            for j in range(sub_batches):  # sequential: slice j sees j' < j
                sl = slice(j * sb, (j + 1) * sb)
                state, kj, kij = one_sub(
                    state, my, q_all[sl], pc_all[sl], lv_all[sl],
                    va_all[sl] if va_all is not None else None)
                keeps.append(kj)
                keep_ins.append(kij)
            keep = jnp.concatenate(keeps)
            keep_in = jnp.concatenate(keep_ins)
        else:
            state, keep, keep_in = one_sub(state, my, q_all, pc_all, lv_all,
                                           va_all,
                                           frees[0] if frees is not None
                                           else None)
        state = jax.tree.map(lambda x: x[None], state)
        if masked:
            return state, keep, keep_in
        return state, keep

    n_in = (5 if masked else 4) + (1 if free_slots else 0)
    out_keep = (P(), P()) if masked else (P(),)
    step = _smap(mesh)(
        local,
        in_specs=(HNSWState(*(P(axis),) * len(HNSWState._fields)),)
        + (P(axis),) * (n_in - 1),
        out_specs=(HNSWState(*(P(axis),) * len(HNSWState._fields)),)
        + out_keep)
    return step
