"""Array-based HNSW for TPU/JAX — the FOLD index (paper §2.2, §4).

CPU HNSW implementations (FAISS/hnswlib) are pointer-chasing structures with
per-node mallocs and locks. That shape is hostile to XLA, so we re-express
HNSW as fixed-capacity dense arrays with functional updates:

  vectors    (cap, W)  uint32   packed signatures (bitmap / raw MinHash)
  pb         (cap,)    int32    cached popcounts (paper §5.2)
  neighbors  (L+1, cap, M0) int32  padded adjacency, -1 = empty slot
  node_level (cap,)    int32    -1 = unused slot
  entry / top_level / count     scalars

Search is the standard greedy-descent + bounded beam, expressed as
`lax.while_loop` over a fixed-size beam with masked argmin selection. The
paper's `efSearch` is literally the expansion budget of the loop — matching
its framing of efSearch as "the number of candidates explored".

Memory/throughput shape of the beam loop (this file's hot path):

  * the per-query visited set is a PACKED uint32 bitset ((cap+31)//32
    words, core/bitset.py) — 8x smaller than the historical (cap,) bool
    mask; `HNSWConfig.packed_visited=False` keeps the bool variant for
    the bit-identical parity tests;
  * each `while_loop` step expands a FRONTIER of up to `HNSWConfig.frontier`
    beam nodes at once, gathering all frontier*M0 neighbor rows and scoring
    them in one fused XOR+popcount distance call (the same tiled shape
    kernels/bitmap_jaccard.py runs on the VPU) instead of dribbling M0 rows
    per step; the efSearch budget counts EXPANSIONS, so the total work is
    unchanged — it is just batched into VPU-sized calls;
  * batched search is CHUNKED BY DEFAULT: `hnsw_search` derives a sane
    `query_chunk` from the capacity when the knob is unset, bounding the
    live visited state at (chunk, (cap+31)//32) words regardless of Q.

Insertion (the ingest half of the paper's online loop) is a TWO-PHASE
BATCHED COMMIT by default: phase A discovers every kept row's per-level
candidates in one chunked vmapped beam-search program against the
pre-batch graph (optionally seeded from the admission step's own search
results — `seed_ids`), and phase B commits the strictly order-dependent
surgery (slot writes, adjacency rows, back-links, entry/top) with one loop
per level over only the rows that link there, with intra-batch links
supplied by merging the batch's earlier rows into each candidate set.
`HNSWConfig.batched_insert=False` keeps the historical per-doc traversal
loop; a single-row batch is bit-identical between the two organizations.

The per-hop hot loop — distances from the query to the gathered neighbor
rows — is exactly the bitmap-Jaccard XOR+popcount computation that
kernels/bitmap_jaccard.py tiles for the VPU. Inside the (vmapped) search we
use the fused jnp form (a frontier gather is one VPU-sized call, too small
for a kernel launch per hop); the kernel carries the bulk paths (in-batch
dedup, flat scoring, distributed shard scan).

Three metrics, selected statically (paper §3.2's three-way comparison):
  bitmap_jaccard  — FOLD: D = 2 px / (pa + pb + px)
  minhash_jaccard — FAISS (Jaccard) baseline: D = 1 - mean(lane equality)
  hamming         — FAISS (Hamming) baseline: D = popcount(xor) / bits
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bitset import (bitset_add, bitset_nbytes, bitset_test,
                               bitset_zeros)

__all__ = ["HNSWConfig", "HNSWState", "hnsw_init", "hnsw_grow",
           "hnsw_insert_batch", "hnsw_search", "hnsw_delete", "hnsw_compact",
           "sample_levels", "METRICS", "auto_query_chunk", "visited_nbytes"]

METRICS = ("bitmap_jaccard", "minhash_jaccard", "hamming")

_INF = jnp.float32(jnp.inf)

# target for the per-chunk visited state of a batched search; the auto
# query_chunk is sized so chunk * visited_nbytes(cfg) stays under this
_VISITED_BUDGET_BYTES = 16 << 20


class HNSWConfig(NamedTuple):
    capacity: int
    words: int                      # W: packed words per vector
    M: int = 16                     # max degree, upper layers
    M0: int = 32                    # max degree, level 0
    ef_construction: int = 64
    ef_search: int = 64
    max_level: int = 4              # levels 0..max_level
    metric: str = "bitmap_jaccard"
    # hnswlib-style diverse neighbor selection at insert time: keep a
    # candidate only if it is closer to the new node than to any already
    # selected neighbor. Improves recall in duplicate-dense clusters (the
    # paper's hardest regime) at a small construction cost.
    select_heuristic: bool = False
    # beam nodes expanded per while_loop step: each step gathers
    # frontier*M0 neighbor rows and scores them in one fused distance call.
    # The efSearch budget counts expansions, not steps, so recall semantics
    # are frontier-independent to first order.
    frontier: int = 4
    # visited-set representation: packed uint32 bitset (8x smaller) vs the
    # historical (capacity,) bool mask. Kept switchable for the parity tests.
    packed_visited: bool = True
    # default query chunking for batched search: None = derive from capacity
    # (bound the visited working set), 0 = never chunk, N = chunk at N.
    query_chunk: int | None = None
    # insertion organization: True (default) = two-phase batched commit —
    # phase A discovers every kept row's per-level candidates in ONE chunked
    # vmapped beam-search program against the pre-batch graph (optionally
    # seeded from the admission step's search results), phase B commits the
    # cheap order-dependent graph surgery, looping only over the (row,
    # level) pairs that link. False = the historical per-doc fori_loop (one
    # full top-down traversal per row), kept for the equivalence tests and
    # as the conservative fallback.
    batched_insert: bool = True

    @property
    def ml(self) -> float:
        return 1.0 / np.log(max(self.M, 2))


class HNSWState(NamedTuple):
    """Dense functional index state.

    `count` is a HIGH-WATER mark: slots < count have been used at some
    point; slots with node_level == -1 below the mark are free-listed
    (reclaimed by hnsw_compact) and re-usable via hnsw_insert_batch's
    `free_slots`. `dead` tombstones occupied slots: a dead node stays
    navigable (the beam traverses it for connectivity, hnswlib-style) but
    is filtered from returned top-k results and from new nodes' adjacency.
    """
    vectors: jnp.ndarray      # (cap, W) uint32
    pb: jnp.ndarray           # (cap,) int32 cached popcounts
    neighbors: jnp.ndarray    # (L+1, cap, M0) int32
    node_level: jnp.ndarray   # (cap,) int32  (-1 = unused / reclaimed slot)
    dead: jnp.ndarray         # (cap,) bool   tombstones (live = lvl>=0 & ~dead)
    entry: jnp.ndarray        # () int32
    top_level: jnp.ndarray    # () int32
    count: jnp.ndarray        # () int32  high-water slot mark


def visited_nbytes(cfg: HNSWConfig) -> int:
    """Per-query visited-set bytes under the configured representation."""
    return bitset_nbytes(cfg.capacity) if cfg.packed_visited else cfg.capacity


def auto_query_chunk(cfg: HNSWConfig) -> int:
    """Pick a query_chunk bounding the batched-search visited state.

    Sized so chunk * visited_nbytes stays under ~16 MiB, clamped to
    [64, 4096] and rounded down to a power of two (shape reuse across
    batch sizes). At small capacities the clamp disables chunking for
    typical service batches; at 1e6+ slots it kicks in hard — which is
    exactly where the historical (Q, capacity) bool mask exploded.

    The 64-query floor is a throughput guard (narrower vmapped chunks
    waste the VPU), so past ~2M slots (packed) the budget is best-effort:
    live visited state grows linearly again at 64 * visited_nbytes —
    still 8x under the bool mask. Pass query_chunk explicitly to trade
    throughput for a harder memory bound.
    """
    per_q = max(visited_nbytes(cfg), 1)
    chunk = max(_VISITED_BUDGET_BYTES // per_q, 1)
    return int(min(4096, max(64, 1 << (chunk.bit_length() - 1))))


def hnsw_init(cfg: HNSWConfig) -> HNSWState:
    cap, W = cfg.capacity, cfg.words
    return HNSWState(
        vectors=jnp.zeros((cap, W), jnp.uint32),
        pb=jnp.zeros((cap,), jnp.int32),
        neighbors=jnp.full((cfg.max_level + 1, cap, cfg.M0), -1, jnp.int32),
        node_level=jnp.full((cap,), -1, jnp.int32),
        dead=jnp.zeros((cap,), jnp.bool_),
        entry=jnp.int32(-1),
        top_level=jnp.int32(-1),
        count=jnp.int32(0),
    )


def abstract_state(cfg: HNSWConfig) -> HNSWState:
    """HNSWState with ShapeDtypeStruct leaves (zero device allocation).

    What the compile-time analyzer (repro.analysis) and launch dry runs
    trace/lower against — the one place the state geometry is derived, so
    a field added to HNSWState is automatically covered by the program
    fingerprints."""
    return jax.eval_shape(lambda: hnsw_init(cfg))


def program_cache_sizes() -> dict[str, int]:
    """Per-program compiled-variant counts for the hot-path entry points.

    Reads the jit caches (no sync). The service surfaces this in stats()
    and the recompilation-budget tests assert on deltas of it: each entry
    should grow by exactly |batch buckets| per index geometry, ever."""
    return {
        "search": hnsw_search._cache_size(),
        "insert": hnsw_insert_batch._cache_size(),
        "delete": hnsw_delete._cache_size(),
        "compact": hnsw_compact._cache_size(),
    }


def hnsw_grow(cfg: HNSWConfig, state: HNSWState,
              new_capacity: int) -> tuple[HNSWConfig, HNSWState]:
    """Functionally re-pad the dense arrays to a larger capacity.

    The graph is preserved exactly: neighbors/levels/entry/count are copied,
    new slots are empty (-1 level, -1 adjacency) and unreachable, so search
    on the grown index returns identical results to the original. Capacity is
    static in the jitted search/insert programs, so the first call after a
    grow recompiles once — the index lifecycle layer (repro.service) grows
    geometrically to bound that to O(log corpus) compiles.
    """
    if new_capacity < cfg.capacity:
        raise ValueError(f"cannot shrink: {new_capacity} < {cfg.capacity}")
    if new_capacity == cfg.capacity:
        return cfg, state
    pad = new_capacity - cfg.capacity
    new_cfg = cfg._replace(capacity=new_capacity)
    new_state = HNSWState(
        vectors=jnp.pad(state.vectors, ((0, pad), (0, 0))),
        pb=jnp.pad(state.pb, (0, pad)),
        neighbors=jnp.pad(state.neighbors, ((0, 0), (0, pad), (0, 0)),
                          constant_values=-1),
        node_level=jnp.pad(state.node_level, (0, pad), constant_values=-1),
        dead=jnp.pad(state.dead, (0, pad)),
        entry=state.entry,
        top_level=state.top_level,
        count=state.count,
    )
    return new_cfg, new_state


def sample_levels(n: int, cfg: HNSWConfig, seed: int = 0) -> np.ndarray:
    """Geometric level assignment, counter-based (deterministic, resumable)."""
    idx = np.arange(n, dtype=np.uint64) + np.uint64(seed) * np.uint64(0x9E3779B9)
    x = idx * np.uint64(0x9E3779B97F4A7C15)
    x ^= x >> np.uint64(29)
    x *= np.uint64(0xBF58476D1CE4E5B9)
    x ^= x >> np.uint64(32)
    u = (x.astype(np.float64) + 1.0) / 2.0**64
    lv = np.floor(-np.log(u) * cfg.ml).astype(np.int32)
    return np.minimum(lv, cfg.max_level)


# ------------------------------------------------------------- visited set
# Thin dispatch over the two visited-set representations. The packed path
# is the production default; the bool path exists so the parity tests can
# assert bit-identical (ids, sims) between the two.
def _visited_new(cfg: HNSWConfig) -> jnp.ndarray:
    if cfg.packed_visited:
        return bitset_zeros(cfg.capacity)
    return jnp.zeros((cfg.capacity,), jnp.bool_)


def _visited_test(cfg: HNSWConfig, vs, ids) -> jnp.ndarray:
    if cfg.packed_visited:
        return bitset_test(vs, ids)
    return vs[jnp.maximum(ids, 0)] & (ids >= 0)


def _visited_add(cfg: HNSWConfig, vs, ids, mask) -> jnp.ndarray:
    """Mark masked ids visited. Masked ids must be unique and unvisited
    (the bitset_add contract); masked-out ids may repeat freely."""
    if cfg.packed_visited:
        return bitset_add(vs, ids, mask)
    # scatter-max is duplicate-safe (bool max == OR), unlike scatter-set
    # whose winner among duplicate indices is unspecified
    return vs.at[jnp.maximum(ids, 0)].max(mask)


# ----------------------------------------------------------------- distance
def _dist_rows(cfg: HNSWConfig, q: jnp.ndarray, qpc: jnp.ndarray,
               vecs: jnp.ndarray, pcs: jnp.ndarray) -> jnp.ndarray:
    """Distance from one query to a batch of stored rows. (K,) f32."""
    if cfg.metric == "bitmap_jaccard":
        px = jnp.sum(jax.lax.population_count(q[None, :] ^ vecs).astype(jnp.int32), -1)
        denom = qpc + pcs + px
        return jnp.where(denom > 0,
                         2.0 * px.astype(jnp.float32) / jnp.maximum(denom, 1),
                         0.0)
    if cfg.metric == "minhash_jaccard":
        return 1.0 - jnp.mean((q[None, :] == vecs).astype(jnp.float32), axis=-1)
    if cfg.metric == "hamming":
        bits = jnp.float32(cfg.words * 32)
        dh = jnp.sum(jax.lax.population_count(q[None, :] ^ vecs).astype(jnp.int32), -1)
        return dh.astype(jnp.float32) / bits
    raise ValueError(f"unknown metric {cfg.metric}")


def _dist_ids(cfg, state: HNSWState, q, qpc, ids) -> jnp.ndarray:
    """Masked distance to node ids; id < 0 -> +inf."""
    safe = jnp.maximum(ids, 0)
    d = _dist_rows(cfg, q, qpc, state.vectors[safe], state.pb[safe])
    return jnp.where(ids >= 0, d, _INF)


def _mask_dead_sorted(state: HNSWState, ids, d):
    """Mask tombstoned ids out of a distance-sorted candidate list.

    Dead nodes are traversed for connectivity but must never be selected —
    not as search results, not as adjacency for new nodes. Masked entries
    become -1/+inf and the list is re-sorted so prefix-takes skip them;
    jnp's stable argsort makes this a no-op permutation when nothing is
    dead (the bit-identity configurations are unaffected)."""
    is_dead = state.dead[jnp.maximum(ids, 0)] & (ids >= 0)
    ids = jnp.where(is_dead, -1, ids)
    d = jnp.where(is_dead, _INF, d)
    order = jnp.argsort(d)
    return ids[order], d[order]


# ------------------------------------------------------------ greedy descent
def _greedy_step(cfg, state, q, qpc, level: int, cur, curd, max_steps: int = 64):
    """ef=1 greedy walk at a (static) level: move to closer neighbor while improving."""
    def cond(c):
        _, _, improved, steps = c
        return improved & (steps < max_steps)

    def body(c):
        cur, curd, _, steps = c
        nbrs = state.neighbors[level, cur]           # (M0,)
        d = _dist_ids(cfg, state, q, qpc, nbrs)
        j = jnp.argmin(d)
        better = d[j] < curd
        return (jnp.where(better, nbrs[j], cur),
                jnp.minimum(curd, d[j]), better, steps + 1)

    cur, curd, _, _ = jax.lax.while_loop(
        cond, body, (cur, curd, jnp.bool_(True), jnp.int32(0)))
    return cur, curd


# ------------------------------------------------------------- beam search
def _search_layer(cfg, state, q, qpc, level: int, ef: int,
                  init_ids, init_dists, visited):
    """Bounded beam search at one (static) level.

    init_ids/init_dists: (E,) seeds (-1 = empty, ids must be distinct).
    Returns beam of size ef (ids, dists) sorted ascending by distance, plus
    the updated visited set. `ef` is the EXPANSION budget — the paper's
    efSearch semantics — independent of how many nodes one while_loop step
    expands: each step pops the `F = min(cfg.frontier, ef)` closest
    unexpanded beam nodes, gathers their F*M0 neighbor rows, and scores the
    fresh ones in one fused distance call.
    """
    E = init_ids.shape[0]
    pad = ef - E
    assert pad >= 0, "ef must be >= number of seeds"
    F = max(1, min(cfg.frontier, ef))
    M0 = cfg.M0
    beam_ids = jnp.concatenate([init_ids, jnp.full((pad,), -1, jnp.int32)])
    beam_d = jnp.concatenate([init_dists, jnp.full((pad,), jnp.inf, jnp.float32)])
    expanded = beam_ids < 0  # empty slots can never be selected
    visited = _visited_add(cfg, visited, init_ids, init_ids >= 0)

    def cond(c):
        beam_ids, beam_d, expanded, visited, n_exp, steps = c
        # steps mirrors n_exp (>= 1 expansion per step) and is a hard
        # termination bound should a no-progress state ever arise
        return jnp.any(~expanded) & (n_exp < ef) & (steps < ef)

    def body(c):
        beam_ids, beam_d, expanded, visited, n_exp, steps = c
        # pop the F closest unexpanded beam nodes (clipped to the budget).
        # Selection is by distance but expansion eligibility is NOT gated
        # on finiteness: an inf-distance seed (search on an empty index)
        # must still be expanded or the loop would never make progress.
        masked = jnp.where(expanded, jnp.inf, beam_d)
        neg, sel = jax.lax.top_k(-masked, F)
        can = ~expanded[sel] & (jnp.arange(F) < (ef - n_exp))
        expanded = expanded.at[sel].set(expanded[sel] | can)
        fids = jnp.where(can, beam_ids[sel], -1)
        # gather all frontier adjacency rows -> one (F*M0,) candidate list
        nbrs = state.neighbors[level, jnp.maximum(fids, 0)]      # (F, M0)
        nbrs = jnp.where((fids >= 0)[:, None], nbrs, -1).reshape(-1)
        # two frontier nodes may share a neighbor: dedup via sort +
        # first-occurrence so each id enters the beam (and the visited
        # scatter) at most once
        order = jnp.argsort(nbrs)
        snb = nbrs[order]
        first = jnp.concatenate([jnp.ones((1,), bool), snb[1:] != snb[:-1]])
        fresh = first & (snb >= 0) & ~_visited_test(cfg, visited, snb)
        visited = _visited_add(cfg, visited, snb, fresh)
        # one fused XOR+popcount distance call over the whole gather
        d = jnp.where(fresh, _dist_ids(cfg, state, q, qpc, snb), jnp.inf)
        # merge beam with fresh neighbors, keep top-ef by distance
        cat_ids = jnp.concatenate([beam_ids, jnp.where(fresh, snb, -1)])
        cat_d = jnp.concatenate([beam_d, d])
        cat_exp = jnp.concatenate([expanded, jnp.zeros((F * M0,), jnp.bool_)])
        neg2, idxs = jax.lax.top_k(-cat_d, ef)
        return (cat_ids[idxs], -neg2, cat_exp[idxs] | (cat_ids[idxs] < 0),
                visited, n_exp + jnp.sum(can, dtype=jnp.int32), steps + 1)

    beam_ids, beam_d, _, visited, _, _ = jax.lax.while_loop(
        cond, body, (beam_ids, beam_d, expanded, visited, jnp.int32(0),
                     jnp.int32(0)))
    order = jnp.argsort(beam_d)
    return beam_ids[order], beam_d[order], visited


def _descend(cfg, state, q, qpc, stop_level: jnp.ndarray):
    """Greedy-descend from the global entry down to stop_level+1 (inclusive)."""
    cur = jnp.maximum(state.entry, 0)
    curd = _dist_ids(cfg, state, q, qpc, state.entry[None])[0]
    for lev in range(cfg.max_level, 0, -1):  # static unroll; level 0 excluded
        active = (lev <= state.top_level) & (lev > stop_level)
        nxt, nxtd = _greedy_step(cfg, state, q, qpc, lev, cur, curd)
        cur = jnp.where(active, nxt, cur)
        curd = jnp.where(active, nxtd, curd)
    return cur, curd


# ---------------------------------------------------------- chunked mapping
def _chunked_map(fn, operands, chunk: int, pad_values=None):
    """Run a batched `fn` over `operands` in chunks along the leading axis.

    The memory-bounding idiom shared by batched search, phase-A candidate
    discovery, and the intra-batch distance matrix: pad to a multiple of
    `chunk`, `lax.map` the function over (n, chunk, ...) slabs, slice the
    padding back off every output. `chunk` falsy or B <= chunk runs `fn`
    directly — chunking never changes results, only the live working set."""
    B = operands[0].shape[0]
    if not chunk or B <= chunk:
        return fn(*operands)
    pad = (-B) % chunk
    n = (B + pad) // chunk
    if pad_values is None:
        pad_values = (0,) * len(operands)
    slabs = tuple(
        jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1), constant_values=v)
        .reshape((n, chunk) + x.shape[1:])
        for x, v in zip(operands, pad_values))
    out = jax.lax.map(lambda xs: fn(*xs), slabs)
    return jax.tree.map(
        lambda y: y.reshape((B + pad,) + y.shape[2:])[:B], out)


# ------------------------------------------------------------------- search
@functools.partial(jax.jit, static_argnames=("cfg", "k", "ef", "query_chunk"))
def hnsw_search(cfg: HNSWConfig, state: HNSWState, queries: jnp.ndarray,
                k: int, ef: int | None = None,
                query_chunk: int | None = None):
    """Batched kNN search.

    queries: (Q, W) uint32. Returns (ids (Q, k) int32, sims (Q, k) f32);
    missing results have id -1 and sim -inf. Similarity = 1 - distance for
    all three metrics (each distance is normalized to [0, 1]). ef is clamped
    to >= k so the result always has k columns.

    Chunked execution is the DEFAULT: the vmapped search carries a
    (Q, visited) working set — historically a (Q, capacity) bool mask,
    which at ingest scale (1e5 queries x 1e6 slots) is terabytes; now a
    packed (Q, (capacity+31)//32) uint32 bitset, and Q is bounded by
    running lax.map over (Q/chunk) vmapped chunks. query_chunk resolution:
    an explicit argument wins, else cfg.query_chunk, else a capacity-derived
    default (auto_query_chunk); 0 disables chunking. Chunking never changes
    results — benchmarks/search_mem.py measures the memory/throughput.
    """
    ef = cfg.ef_search if ef is None else ef
    ef = max(ef, k)      # k columns are promised regardless of the budget
    if query_chunk is None:
        query_chunk = (cfg.query_chunk if cfg.query_chunk is not None
                       else auto_query_chunk(cfg))
    qpcs = jnp.sum(jax.lax.population_count(queries).astype(jnp.int32), -1)

    def one(q, qpc):
        with jax.named_scope("fold.search.descend"):
            cur, curd = _descend(cfg, state, q, qpc, jnp.int32(0))
        with jax.named_scope("fold.search.beam"):
            visited = _visited_new(cfg)
            ids, d, _ = _search_layer(cfg, state, q, qpc, 0, ef,
                                      cur[None], curd[None], visited)
            # tombstoned nodes stay navigable inside the beam (connectivity)
            # but are masked out of the returned top-k
            ids, d = _mask_dead_sorted(state, ids, d)
            ids, d = ids[:k], d[:k]
            empty = state.count == 0
            ids = jnp.where(empty | (ids < 0) | ~jnp.isfinite(d), -1, ids)
            sims = jnp.where(ids >= 0, 1.0 - d, -jnp.inf)
        return ids, sims

    return _chunked_map(jax.vmap(one), (queries, qpcs), query_chunk)


# ------------------------------------------------------------------- insert
def _select_diverse(cfg, state, cand_ids, cand_d, m_l: int):
    """hnswlib neighbor-selection heuristic over distance-sorted candidates:
    candidate c survives iff d(c, q) < min_{s in selected} d(c, s).

    cand_ids/cand_d: (E,) sorted ascending, -1/-inf padded. Returns (E,)
    ids with non-selected slots set to -1 (selected count <= m_l).
    """
    with jax.named_scope("fold.select_diverse"):
        E = cand_ids.shape[0]
        safe = jnp.maximum(cand_ids, 0)
        vecs = state.vectors[safe]
        pcs = state.pb[safe]
        # pairwise candidate-candidate distances (E x E); rows for invalid
        # ids are never consulted (their selection is masked out below)
        cc = jax.vmap(lambda v, p: _dist_rows(cfg, v, p, vecs, pcs))(vecs,
                                                                      pcs)

        def body(i, carry):
            selected, count = carry
            cand_ok = (cand_ids[i] >= 0) & (count < m_l)
            # distance to the closest already-selected neighbor
            dsel = jnp.min(jnp.where(selected, cc[i], jnp.inf))
            diverse = cand_d[i] < dsel
            take = cand_ok & diverse
            return selected.at[i].set(take), count + take.astype(jnp.int32)

        selected, _ = jax.lax.fori_loop(
            0, E, body, (jnp.zeros((E,), jnp.bool_), jnp.int32(0)))
        return jnp.where(selected, cand_ids, -1)


def _prune_row(cfg, state, node, level: int, cand_ids, cand_d, m_l: int):
    """Write node's adjacency row at `level`: keep the m_l closest candidates
    (or the diverse subset when select_heuristic is on)."""
    if cfg.select_heuristic:
        div_ids = _select_diverse(cfg, state, cand_ids, cand_d, m_l)
        div_d = jnp.where(div_ids >= 0, cand_d, jnp.inf)
        neg, idxs = jax.lax.top_k(-div_d, cfg.M0)
        keep_ids = jnp.where(jnp.isfinite(-neg), div_ids[idxs], -1)
        return state._replace(
            neighbors=state.neighbors.at[level, node].set(keep_ids))
    neg, idxs = jax.lax.top_k(-cand_d, cfg.M0)
    keep_ids = cand_ids[idxs]
    keep_d = -neg
    slot = jnp.arange(cfg.M0)
    keep_ids = jnp.where((slot < m_l) & jnp.isfinite(keep_d), keep_ids, -1)
    return state._replace(
        neighbors=state.neighbors.at[level, node].set(keep_ids))


def _link_back(cfg, state, new_id, level: int, sel_ids, m_l: int):
    """Add new_id into each selected neighbor's row, pruning to m_l.

    Mirrors hnswlib's mutuallyConnectNewElement: while the neighbor's row
    has room the new id is simply merged in (plain top-k keeps every finite
    candidate), but once the row would overflow AND cfg.select_heuristic is
    on, the row is re-selected with the same diversity heuristic the forward
    rows use (_select_diverse). Back-links used to always prune by plain
    top-k, silently ignoring the heuristic — which re-densified exactly the
    duplicate clusters the heuristic exists to keep navigable.

    The per-neighbor updates are independent — sel_ids are distinct and
    each update reads only its own adjacency row (plus immutable vectors) —
    so all rows are recomputed vectorized and committed in one scatter
    instead of the historical per-neighbor lax.scan."""
    S = sel_ids.shape[0]
    safe = jnp.maximum(sel_ids, 0)
    rows = state.neighbors[level, safe]                      # (S, M0)
    nbv = state.vectors[safe]
    nbpc = state.pb[safe]
    cand_ids = jnp.concatenate(
        [rows, jnp.broadcast_to(new_id, (S,))[:, None]], axis=1)  # (S, M0+1)
    d = jax.vmap(lambda v, p, c: _dist_ids(cfg, state, v, p, c))(
        nbv, nbpc, cand_ids)

    neg, idxs = jax.lax.top_k(-d, cfg.M0)                    # (S, M0)
    keep = jnp.take_along_axis(cand_ids, idxs, axis=1)
    new_rows = jnp.where(
        (jnp.arange(cfg.M0)[None, :] < m_l) & jnp.isfinite(-neg), keep, -1)

    if cfg.select_heuristic:
        def heur_one(c_ids, c_d):
            order = jnp.argsort(c_d)         # _select_diverse wants the
            ci, cd = c_ids[order], c_d[order]    # candidates sorted by d
            div = _select_diverse(cfg, state, ci, cd, m_l)
            div_d = jnp.where(div >= 0, cd, jnp.inf)
            hneg, hidx = jax.lax.top_k(-div_d, cfg.M0)
            return jnp.where(jnp.isfinite(-hneg), div[hidx], -1)

        heur_rows = jax.vmap(heur_one)(cand_ids, d)
        overfull = jnp.sum((cand_ids >= 0).astype(jnp.int32), axis=1) > m_l
        new_rows = jnp.where(overfull[:, None], heur_rows, new_rows)

    valid = sel_ids >= 0
    idx = jnp.where(valid, sel_ids, cfg.capacity)            # OOB -> dropped
    return state._replace(
        neighbors=state.neighbors.at[level, idx].set(
            jnp.where(valid[:, None], new_rows, rows), mode="drop"))


def _insert_one(cfg: HNSWConfig, state: HNSWState, vec, pc, level, slot=None):
    """Insert a single vector with a pre-sampled level. Pure function.

    slot: explicit target slot (reclaimed free slots < count are legal);
    None uses the next fresh slot. count keeps high-water semantics —
    writing a free-listed slot below the mark does not advance it."""
    idx = state.count if slot is None else slot
    new_count = (state.count + 1 if slot is None
                 else jnp.maximum(state.count, slot + 1))
    state = state._replace(
        vectors=state.vectors.at[idx].set(vec),
        pb=state.pb.at[idx].set(pc),
        node_level=state.node_level.at[idx].set(level),
        dead=state.dead.at[idx].set(False),
        count=new_count,
    )

    def first(state):
        return state._replace(entry=idx, top_level=level)

    def connect(state):
        cur, curd = _descend(cfg, state, vec, pc, level)
        top = state.top_level  # frozen for this insert
        carry = (state, cur[None], curd[None])
        for lev in range(cfg.max_level, -1, -1):  # static unroll
            m_l = cfg.M0 if lev == 0 else cfg.M

            def do(carry, lev=lev, m_l=m_l):
                st, s_ids, s_d = carry
                visited = _visited_new(cfg)
                cand_ids, cand_d, _ = _search_layer(
                    cfg, st, vec, pc, lev, cfg.ef_construction,
                    s_ids, s_d, visited)
                # new nodes must link only to LIVE nodes: tombstoned beam
                # entries are masked out before any selection
                cand_ids, cand_d = _mask_dead_sorted(st, cand_ids, cand_d)
                # the beam is distance-sorted with -1 in empty slots, so the
                # first m_l entries ARE the selected back-link neighbors
                sel = cand_ids[:m_l]
                st = _prune_row(cfg, st, idx, lev, cand_ids, cand_d, m_l)
                st = _link_back(cfg, st, idx, lev, sel, m_l)
                # seed the next level down with the best candidate found here
                return (st, cand_ids[:1], cand_d[:1])

            active = lev <= jnp.minimum(level, top)
            carry = jax.lax.cond(active, do, lambda c: c, carry)
        state = carry[0]
        # raise entry point if the new node's level exceeds the current top
        higher = level > top
        return state._replace(
            entry=jnp.where(higher, idx, state.entry),
            top_level=jnp.maximum(top, level))

    return jax.lax.cond(state.entry < 0, first, connect, state)


# ----------------------------------------------- two-phase batched insert
def _pairwise_dists(cfg: HNSWConfig, vecs, pcs, chunk: int) -> jnp.ndarray:
    """(B, B) distance matrix among the batch rows, chunked on the query
    dim so the fused XOR+popcount temp stays bounded for large ingests."""
    def row(q, qpc):
        return _dist_rows(cfg, q, qpc, vecs, pcs)

    return _chunked_map(jax.vmap(row), (vecs, pcs), chunk)


def _discover_candidates(cfg: HNSWConfig, state: HNSWState, vecs, pcs,
                         levels, seed_ids, chunk: int):
    """Phase A: per-row, per-level candidate discovery vs the PRE-BATCH
    graph — one chunked vmapped program (the memory-lean search machinery)
    instead of B sequential top-down traversals.

    seed_ids: optional (B, S) int32 — the admission step's search results
    for these exact rows (step ③ just walked the graph for them); they seed
    the level-0 beam so construction starts from the query's neighborhood
    instead of re-finding it from the entry point. S must be < ef_construction.
    Returns (cand_ids, cand_d): (B, L+1, E) sorted ascending per level;
    inactive levels / empty graph come back -1 / +inf.
    """
    E = cfg.ef_construction
    L1 = cfg.max_level + 1

    def one(q, qpc, level, seeds):
        cur, curd = _descend(cfg, state, q, qpc, level)
        top = state.top_level
        s_ids, s_d = cur[None], curd[None]
        out_ids = jnp.full((L1, E), -1, jnp.int32)
        out_d = jnp.full((L1, E), jnp.inf, jnp.float32)
        # NOTE: no lax.cond around the per-level search. Under vmap a cond
        # runs both branches anyway, and its batched lowering of the inner
        # while_loop is an order of magnitude slower than running the search
        # unconditionally — so every level's search executes (inactive
        # levels exhaust their tiny beams immediately) and only the CARRY
        # and the outputs are masked, which preserves the sequential
        # semantics exactly: the topmost active level still starts from the
        # descend result, lower active levels from the level above's best.
        for lev in range(cfg.max_level, -1, -1):   # static unroll
            init_ids, init_d = s_ids, s_d
            if lev == 0 and seeds is not None:
                # merge the step-③ seeds into the initial beam; the
                # _search_layer seed contract wants distinct ids, so
                # repeats (seed == descend result) are masked out
                sd = _dist_ids(cfg, state, q, qpc, seeds)
                cat = jnp.concatenate([s_ids, seeds])
                catd = jnp.concatenate([s_d, sd])
                order = jnp.argsort(cat)
                so, sod = cat[order], catd[order]
                dup = jnp.concatenate(
                    [jnp.zeros((1,), bool), so[1:] == so[:-1]])
                init_ids = jnp.where(dup, -1, so)
                init_d = jnp.where(dup, jnp.inf, sod)
            visited = _visited_new(cfg)
            c_ids, c_d, _ = _search_layer(cfg, state, q, qpc, lev, E,
                                          init_ids, init_d, visited)
            active = lev <= jnp.minimum(level, top)
            # seed the next level down with the best candidate found here
            s_ids = jnp.where(active, c_ids[:1], s_ids)
            s_d = jnp.where(active, c_d[:1], s_d)
            out_ids = out_ids.at[lev].set(jnp.where(active, c_ids, -1))
            out_d = out_d.at[lev].set(jnp.where(active, c_d, jnp.inf))
        # an unreachable / empty-graph "candidate" surfaces as +inf distance
        # (e.g. the entry placeholder when entry < 0): it is no candidate
        out_ids = jnp.where(jnp.isfinite(out_d), out_ids, -1)
        return out_ids, jnp.where(out_ids >= 0, out_d, jnp.inf)

    if seed_ids is None:
        return _chunked_map(jax.vmap(lambda a, b, c: one(a, b, c, None)),
                            (vecs, pcs, levels), chunk)
    return _chunked_map(jax.vmap(one), (vecs, pcs, levels, seed_ids), chunk,
                        pad_values=(0, 0, 0, -1))


def _merge_candidates(cfg: HNSWConfig, state: HNSWState, levels, admit,
                      slots, cand_ids, cand_d, pair_d):
    """Vectorized candidate merge + neighbor selection for the whole batch.

    For every (row, level): merge the phase-A graph candidates with the
    batch's own EARLIER admitted rows that exist at that level (intra-batch
    links — at levels above the pre-batch top they are the only nodes, so
    the merged set is complete there; slot ids >= the pre-batch count never
    collide with graph candidate ids < it). From the merged distance-sorted
    list derive the two order-independent products of an insert:

      fwd (B, L+1, M0)  the new node's own adjacency row per level
                        (exactly _prune_row's selection, heuristic included)
      sel (B, L+1, M0)  the back-link targets (closest m_l, -1 padded)

    Neither depends on the commit-time graph state — selection reads only
    vectors (already slot-written) — so all of it runs as one vectorized
    program, leaving only back-links and entry/top updates to the commit.
    `state` must be the slot-written state (batch vectors visible)."""
    B = slots.shape[0]
    E = cand_ids.shape[-1]
    jidx = jnp.arange(B, dtype=jnp.int32)
    earlier = (jidx[None, :] < jidx[:, None]) & admit[None, :]   # (B, B)

    fwd_levels, sel_levels = [], []
    for lev in range(cfg.max_level + 1):
        m_l = cfg.M0 if lev == 0 else cfg.M
        bmask = earlier & (levels[None, :] >= lev)
        b_ids = jnp.where(bmask, slots[None, :], -1)
        b_d = jnp.where(bmask, pair_d, jnp.inf)
        cat_ids = jnp.concatenate([cand_ids[:, lev], b_ids], axis=1)
        cat_d = jnp.concatenate([cand_d[:, lev], b_d], axis=1)
        neg, ix = jax.lax.top_k(-cat_d, E)                       # (B, E)
        m_ids = jnp.where(jnp.isfinite(-neg),
                          jnp.take_along_axis(cat_ids, ix, axis=1), -1)
        m_d = -neg
        if cfg.select_heuristic:
            div = jax.vmap(
                lambda ci, cd: _select_diverse(cfg, state, ci, cd, m_l))(
                    m_ids, m_d)
            div_d = jnp.where(div >= 0, m_d, jnp.inf)
            hneg, hidx = jax.lax.top_k(-div_d, cfg.M0)
            fwd = jnp.where(jnp.isfinite(-hneg),
                            jnp.take_along_axis(div, hidx, axis=1), -1)
        else:
            fwd = jnp.where(
                (jnp.arange(cfg.M0)[None, :] < m_l)
                & jnp.isfinite(m_d[:, :cfg.M0]), m_ids[:, :cfg.M0], -1)
        # distance-sorted with -1 in empty slots: the first m_l entries ARE
        # the back-link targets (M0-padded so levels stack uniformly)
        sel_levels.append(m_ids[:, :cfg.M0])
        fwd_levels.append(fwd)
    return (jnp.stack(fwd_levels, axis=1),    # (B, L+1, M0)
            jnp.stack(sel_levels, axis=1))


def _commit_batch(cfg: HNSWConfig, state: HNSWState, levels, admit, slots,
                  fwd, sel) -> HNSWState:
    """Phase B: the cheap, strictly order-dependent graph surgery — per
    admitted row and level it links at: write the precomputed adjacency
    row, back-link into the selected neighbors (_link_back); then entry/top.
    No graph traversals and no candidate selection happen here.

    Only the (row, level) pairs that link are run. Row r links at level l
    iff it is admitted and l <= min(level[r], top_before[r]), where
    top_before[r] is the running top it sees: the pre-batch top raised by
    the levels of the admitted rows before it (an exclusive cumulative
    max), so every pair's activity, and the final entry and top, are known
    before any write. Levels do not interact here — a pass at level l
    reads and writes only neighbors[l] (vectors and pb are already
    slot-written) — so only the order of rows within a level matters. One
    loop runs level 0's active rows in ascending row order, a second the
    upper levels' active (level, row) pairs, level by level and rows
    ascending within each: the same order as the sequential inserts. Each
    loop's width m_l is static; the upper levels share one loop (their
    level is read from the pair), which keeps the insert program within
    its while-loop budget of 12 (a loop per level would compile 14).

    The loops are while loops with a dynamic trip count, carrying the state
    in place: a lax.cond over the carried state would make XLA materialize
    both branch outputs (copies of the dense neighbor arrays, every step).
    The sequential "first node" case needs no special branch: a row that
    sees a running top of -1 links at no level, and the entry/top rule —
    entry moves to the last row whose level exceeds its running top —
    covers it."""
    B = slots.shape[0]
    rows = jnp.arange(B, dtype=jnp.int32)
    lvl = jnp.where(admit, levels, -1)
    top_before = jnp.maximum(state.top_level, jnp.concatenate(
        [jnp.full((1,), -1, jnp.int32), jax.lax.cummax(lvl)[:-1]]))
    reach = jnp.where(admit, jnp.minimum(levels, top_before), -1)
    raised = jnp.max(jnp.where(lvl > top_before, rows, -1))
    state = state._replace(
        entry=jnp.where(raised >= 0, slots[jnp.maximum(raised, 0)],
                        state.entry),
        top_level=jnp.maximum(state.top_level, jnp.max(lvl)))

    def link(st, lo: int, n_lev: int, m_l: int):
        """Run the active pairs of levels lo..lo+n_lev-1 at width m_l."""
        act = reach[None, :] >= lo + jnp.arange(n_lev, dtype=jnp.int32)[:, None]
        pairs = jnp.nonzero(act.reshape(-1), size=act.size, fill_value=0)[0]

        def body(i, st):
            r = pairs[i] % B
            lev = lo if n_lev == 1 else lo + pairs[i] // B   # static at 0
            st = st._replace(
                neighbors=st.neighbors.at[lev, slots[r]].set(fwd[r, lev]))
            return _link_back(cfg, st, slots[r], lev, sel[r, lev, :m_l], m_l)

        return jax.lax.fori_loop(0, jnp.sum(act, dtype=jnp.int32), body, st)

    state = link(state, 0, 1, cfg.M0)
    if cfg.max_level > 0:
        state = link(state, 1, cfg.max_level, cfg.M)
    return state


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1,))
def hnsw_insert_batch(cfg: HNSWConfig, state: HNSWState, vecs: jnp.ndarray,
                      pcs: jnp.ndarray, levels: jnp.ndarray,
                      mask: jnp.ndarray,
                      seed_ids: jnp.ndarray | None = None,
                      free_slots: jnp.ndarray | None = None
                      ) -> tuple[HNSWState, jnp.ndarray]:
    """Insert a batch in deterministic row order. mask=False skips.

    vecs: (B, W) uint32; pcs: (B,) int32; levels: (B,) int32 (pre-sampled);
    mask: (B,) bool — only True rows are inserted (duplicates stay out).
    seed_ids: optional (B, S) int32, S < ef_construction — per-row graph
    neighborhoods already known to the caller (the admission loop's step-③
    search results); consumed by the batched path to seed candidate
    discovery so the graph is not re-traversed from the top for rows the
    pipeline just searched. The per-doc path ignores them.
    free_slots: optional (F,) int32, -1 padded — reclaimed slot ids (from
    hnsw_compact: node_level == -1 below the count mark, fully unlinked)
    consumed FIRST, in order, before fresh capacity. Because reclaimed
    slots are unreachable in the pre-batch graph, phase-A candidate ids
    can never collide with a reused slot. `count` keeps its high-water
    semantics, so reuse does not advance it.

    Two organizations, selected by `cfg.batched_insert` (see HNSWConfig):
    the default two-phase batched commit discovers candidates for ALL rows
    in one chunked vmapped program against the pre-batch graph and then
    loops over the linking rows doing only adjacency writes; the per-doc
    path runs one full traversal per row inside a fori_loop. Both assign the same
    slots to the same rows; a single-row batch is bit-identical between
    them (phase A degenerates to the sequential search).

    Returns (state, n_inserted) where n_inserted is a () int32 device scalar
    counting the rows ACTUALLY inserted. When the index is full (no free
    slots left AND the high-water mark hits capacity), masked rows are
    skipped — n_inserted < mask.sum() is the caller's overflow signal; the
    `repro.index` backends refuse the batch rather than let a verdict
    claim admission for a dropped row (see DedupBackend.insert).
    """
    mask = mask.astype(jnp.bool_)
    count0 = state.count
    # slot assignment mirrors the sequential order exactly: kept rows drain
    # the free list first, then fill consecutive fresh slots; rows past
    # capacity are skipped (overflow signal)
    offs = jnp.cumsum(mask.astype(jnp.int32)) - 1
    if free_slots is None:
        slots = count0 + offs
        fresh = mask
    else:
        free_slots = jnp.asarray(free_slots, jnp.int32)
        n_free = jnp.sum(free_slots >= 0, dtype=jnp.int32)
        use_free = (offs >= 0) & (offs < n_free)
        gather = jnp.clip(offs, 0, free_slots.shape[0] - 1)
        slots = jnp.where(use_free, free_slots[gather],
                          count0 + offs - n_free)
        fresh = mask & ~use_free
    admit = mask & (slots >= 0) & (slots < cfg.capacity)
    n_ins = jnp.sum(admit, dtype=jnp.int32)
    # only FRESH slots advance the high-water mark
    new_count = count0 + jnp.sum(admit & fresh, dtype=jnp.int32)

    if not cfg.batched_insert:
        def body(i, carry):
            st, n = carry

            def do(c):
                st, n = c
                return (_insert_one(cfg, st, vecs[i], pcs[i], levels[i],
                                    slot=slots[i]), n + 1)

            return jax.lax.cond(admit[i], do, lambda c: c, (st, n))

        return jax.lax.fori_loop(0, vecs.shape[0], body,
                                 (state, jnp.int32(0)))

    # ---- batched two-phase commit
    chunk = (cfg.query_chunk if cfg.query_chunk is not None
             else auto_query_chunk(cfg))
    if seed_ids is not None:
        seed_ids = jnp.asarray(seed_ids, jnp.int32)[:, :cfg.ef_construction - 1]
    # phase A runs against the pre-batch graph (reads only graph-reachable
    # rows — never a reclaimed slot — so the bulk slot write below cannot
    # alias it)
    with jax.named_scope("fold.insert.discover"):
        cand_ids, cand_d = _discover_candidates(cfg, state, vecs, pcs,
                                                levels, seed_ids, chunk)
        # new nodes link only to LIVE candidates: tombstoned graph nodes are
        # masked to -1/+inf (the top-k merge in _merge_candidates drops them)
        cand_dead = state.dead[jnp.maximum(cand_ids, 0)] & (cand_ids >= 0)
        cand_ids = jnp.where(cand_dead, -1, cand_ids)
        cand_d = jnp.where(cand_dead, jnp.inf, cand_d)
    with jax.named_scope("fold.insert.merge"):
        pair_d = _pairwise_dists(cfg, vecs, pcs, chunk)
        levels = jnp.asarray(levels, jnp.int32)
        safe = jnp.where(admit, slots, cfg.capacity)  # OOB rows are dropped
        state = state._replace(
            vectors=state.vectors.at[safe].set(vecs, mode="drop"),
            pb=state.pb.at[safe].set(pcs, mode="drop"),
            node_level=state.node_level.at[safe].set(levels, mode="drop"),
            dead=state.dead.at[safe].set(False, mode="drop"),
            count=new_count)
        fwd, sel = _merge_candidates(cfg, state, levels, admit, slots,
                                     cand_ids, cand_d, pair_d)
    with jax.named_scope("fold.insert.commit"):
        state = _commit_batch(cfg, state, levels, admit, slots, fwd, sel)
    return state, n_ins


# ------------------------------------------------------- delete & compact
@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1,))
def hnsw_delete(cfg: HNSWConfig, state: HNSWState,
                ids: jnp.ndarray) -> tuple[HNSWState, jnp.ndarray]:
    """Tombstone a batch of node ids. O(D) scatter — no graph surgery.

    ids: (D,) int32, -1 padded; out-of-range, unused, and already-dead ids
    are ignored (callers dedup host-side; duplicate LIVE ids in one call
    would be double-counted). Dead nodes stay navigable ghosts — the beam
    traverses them for connectivity, hnswlib-style — but are masked from
    returned top-k (hnsw_search) and from new nodes' adjacency
    (hnsw_insert_batch / _insert_one). Their slots are NOT reusable until
    hnsw_compact unlinks them. Returns (state, n_newly_dead).
    """
    ids = jnp.asarray(ids, jnp.int32)
    safe = jnp.clip(ids, 0, cfg.capacity - 1)
    valid = ((ids >= 0) & (ids < cfg.capacity)
             & (state.node_level[safe] >= 0) & ~state.dead[safe])
    tgt = jnp.where(valid, ids, cfg.capacity)            # OOB -> dropped
    state = state._replace(dead=state.dead.at[tgt].set(True, mode="drop"))
    return state, jnp.sum(valid, dtype=jnp.int32)


def _repair_level(cfg: HNSWConfig, state: HNSWState, live, lev: int,
                  m_l: int, chunk: int):
    """Rebuild the level-`lev` adjacency rows that reference a dead node.

    For each such row the candidate pool is its own live neighbors plus its
    live neighbors-of-neighbors (the hnswlib repairConnectionsForUpdate
    idea): dead hubs are bridged by wiring their live endpoints together.
    Selection reuses the insert-time policy (_select_diverse when
    cfg.select_heuristic, else closest-m_l), so a repaired row obeys the
    same invariants as a freshly built one. Rows with no dead references
    are returned unchanged. Returns the (cap, M0) repaired row matrix.
    """
    rows = state.neighbors[lev]                                # (cap, M0)
    K = cfg.M0 * (1 + cfg.M0)
    E = min(K, max(cfg.ef_construction, cfg.M0))

    def one(node, row):
        nb_dead = state.dead[jnp.maximum(row, 0)] & (row >= 0)
        # pool: own live neighbors + every neighbor's neighbors (live only)
        hops = state.neighbors[lev, jnp.maximum(row, 0)]       # (M0, M0)
        hops = jnp.where((row >= 0)[:, None], hops, -1)
        pool = jnp.concatenate([row, hops.reshape(-1)])        # (K,)
        ok = ((pool >= 0) & live[jnp.maximum(pool, 0)] & (pool != node))
        pool = jnp.where(ok, pool, -1)
        # dedup: sort ids, keep first occurrence of each
        srt = jnp.sort(pool)
        dup = jnp.concatenate([jnp.zeros((1,), bool), srt[1:] == srt[:-1]])
        pool = jnp.where(dup, -1, srt)
        d = _dist_ids(cfg, state, state.vectors[node], state.pb[node], pool)
        neg, ix = jax.lax.top_k(-d, E)
        c_ids = jnp.where(jnp.isfinite(-neg), pool[ix], -1)
        c_d = -neg
        if cfg.select_heuristic:
            div = _select_diverse(cfg, state, c_ids, c_d, m_l)
            div_d = jnp.where(div >= 0, c_d, jnp.inf)
            hneg, hidx = jax.lax.top_k(-div_d, cfg.M0)
            new_row = jnp.where(jnp.isfinite(-hneg), div[hidx], -1)
        else:
            new_row = jnp.where(
                (jnp.arange(cfg.M0) < m_l) & jnp.isfinite(c_d[:cfg.M0]),
                c_ids[:cfg.M0], -1)
        needs = (live[node] & (state.node_level[node] >= lev)
                 & jnp.any(nb_dead))
        return jnp.where(needs, new_row, row)

    nodes = jnp.arange(cfg.capacity, dtype=jnp.int32)
    return _chunked_map(jax.vmap(one), (nodes, rows), chunk,
                        pad_values=(0, -1))


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1,))
def hnsw_compact(cfg: HNSWConfig, state: HNSWState
                 ) -> tuple[HNSWState, jnp.ndarray]:
    """Online compaction: repair adjacency around tombstoned nodes, then
    unlink them so their slots become free-listed (node_level == -1 below
    the count high-water mark — reusable via hnsw_insert_batch free_slots).

    Per level, every live row referencing a dead node is rebuilt from its
    live neighbors-of-neighbors (_repair_level); then dead slots are fully
    unlinked (adjacency cleared, level -> -1, dead flag cleared) and the
    entry point is re-elected if it was tombstoned or out-ranked. `count`
    shrinks only when the tail itself died — interior frees keep the
    high-water mark. Returns (state, n_reclaimed).
    """
    qc = cfg.query_chunk if cfg.query_chunk is not None else auto_query_chunk(cfg)
    chunk = max(64, min(qc, 1024))
    dead0 = state.dead
    live = (state.node_level >= 0) & ~dead0
    repaired = [
        _repair_level(cfg, state, live, lev, cfg.M0 if lev == 0 else cfg.M,
                      chunk)
        for lev in range(cfg.max_level + 1)]
    nbrs = jnp.stack(repaired, axis=0)                   # (L+1, cap, M0)
    # unlink the dead: clear their rows and drop any stale reference
    nbrs = jnp.where(dead0[None, :, None], -1, nbrs)
    ref_dead = dead0[jnp.maximum(nbrs, 0)] & (nbrs >= 0)
    nbrs = jnp.where(ref_dead, -1, nbrs)
    node_level = jnp.where(dead0, -1, state.node_level)
    # entry re-election: keep the current entry iff it is live and still at
    # the top; otherwise promote the first node of the new top level
    ar = jnp.arange(cfg.capacity, dtype=jnp.int32)
    lv = jnp.where(live, node_level, -1)
    top = jnp.max(lv)
    any_live = top >= 0
    esafe = jnp.clip(state.entry, 0, cfg.capacity - 1)
    keep_entry = ((state.entry >= 0) & live[esafe]
                  & (node_level[esafe] >= top))
    entry = jnp.where(any_live,
                      jnp.where(keep_entry, state.entry,
                                jnp.argmax(lv).astype(jnp.int32)),
                      jnp.int32(-1))
    count = jnp.max(jnp.where(live, ar + 1, 0)).astype(jnp.int32)
    state = state._replace(
        neighbors=nbrs,
        node_level=node_level,
        dead=jnp.zeros_like(dead0),
        entry=entry,
        top_level=jnp.where(any_live, top, jnp.int32(-1)),
        count=count)
    return state, jnp.sum(dead0, dtype=jnp.int32)
