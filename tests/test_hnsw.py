"""HNSW correctness: recall vs brute force, the paper's self-search
diagnostic, structural invariants, and the batched-insert equivalence
sweep (two-phase commit vs the per-doc path)."""
import types

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.bitmap import pack_bitmaps, popcount, pairwise_bitmap_jaccard
from repro.core.hnsw import (HNSWConfig, hnsw_compact, hnsw_delete, hnsw_init,
                             hnsw_insert_batch, hnsw_search, sample_levels)
from repro.core.hnsw import _link_back

RNG = np.random.default_rng(3)


def _corpus(n, dup_rate=0.3, H=112):
    sigs = RNG.integers(0, 2**32, (n, H), dtype=np.uint32)
    for i in range(n):
        if i > 10 and RNG.random() < dup_rate:
            j = RNG.integers(0, i)
            sigs[i] = sigs[j].copy()
            lanes = RNG.choice(H, RNG.integers(3, 20), replace=False)
            sigs[i, lanes] = RNG.integers(0, 2**32, len(lanes), dtype=np.uint32)
    return sigs


def _build(sigs, metric="bitmap_jaccard", **kw):
    T = 2048
    if metric == "bitmap_jaccard":
        vecs = pack_bitmaps(jnp.asarray(sigs), T=T)
        pcs = popcount(vecs)
    else:
        vecs = jnp.asarray(sigs)
        pcs = jnp.zeros(len(sigs), jnp.int32)
    cfg = HNSWConfig(capacity=1024, words=vecs.shape[1], M=12, M0=24,
                     ef_construction=40, ef_search=40, max_level=3,
                     metric=metric, **kw)
    state = hnsw_init(cfg)
    levels = jnp.asarray(sample_levels(len(sigs), cfg))
    state, _ = hnsw_insert_batch(cfg, state, vecs, pcs, levels,
                                 jnp.ones(len(sigs), bool))
    return cfg, state, vecs


def test_self_search_bitmap_high_raw_low():
    """Paper §6.3: FOLD self-found 98.7%; FAISS (Jaccard) only 16.8%."""
    sigs = _corpus(400, dup_rate=0.4)
    cfg, state, vecs = _build(sigs, "bitmap_jaccard")
    ids, _ = hnsw_search(cfg, state, vecs, k=4)
    found_bitmap = np.mean([i in set(np.asarray(ids[i])) for i in range(400)])
    cfg2, state2, vecs2 = _build(sigs, "minhash_jaccard")
    ids2, _ = hnsw_search(cfg2, state2, vecs2, k=4)
    found_raw = np.mean([i in set(np.asarray(ids2[i])) for i in range(400)])
    assert found_bitmap > 0.9, found_bitmap
    assert found_raw < 0.7, found_raw
    assert found_bitmap > found_raw + 0.3   # the paper's core claim


def test_knn_recall_vs_brute_force():
    sigs = _corpus(500, dup_rate=0.3)
    cfg, state, vecs = _build(sigs)
    ids, sims = hnsw_search(cfg, state, vecs, k=4)
    full = np.asarray(pairwise_bitmap_jaccard(vecs, vecs))
    gt = np.argsort(-full, axis=1)[:, :4]
    rec = np.mean([len(set(gt[i]) & set(np.asarray(ids[i]))) / 4
                   for i in range(len(sigs))])
    assert rec > 0.85, rec


def test_returned_sims_match_metric():
    sigs = _corpus(200)
    cfg, state, vecs = _build(sigs)
    ids, sims = hnsw_search(cfg, state, vecs, k=4)
    full = np.asarray(pairwise_bitmap_jaccard(vecs, vecs))
    ids_np, sims_np = np.asarray(ids), np.asarray(sims)
    for i in range(0, 200, 17):
        for j, s in zip(ids_np[i], sims_np[i]):
            if j >= 0:
                np.testing.assert_allclose(s, full[i, j], atol=1e-5)


def test_masked_insert_skips():
    sigs = _corpus(100)
    vecs = pack_bitmaps(jnp.asarray(sigs), T=2048)
    pcs = popcount(vecs)
    cfg = HNSWConfig(capacity=256, words=64, M=8, M0=16, ef_construction=16,
                     ef_search=16, max_level=2)
    state = hnsw_init(cfg)
    mask = np.zeros(100, bool)
    mask[::2] = True
    levels = jnp.asarray(sample_levels(100, cfg))
    state, n_ins = hnsw_insert_batch(cfg, state, vecs, pcs, levels,
                                     jnp.asarray(mask))
    assert int(state.count) == 50 == int(n_ins)


def test_capacity_guard():
    """The raw primitive stops at capacity but REPORTS the shortfall: the
    returned n_inserted is the caller's overflow signal (the repro.index
    backends turn it into a loud refusal)."""
    sigs = _corpus(40)
    vecs = pack_bitmaps(jnp.asarray(sigs), T=1024)
    pcs = popcount(vecs)
    cfg = HNSWConfig(capacity=16, words=32, M=4, M0=8, ef_construction=8,
                     ef_search=8, max_level=2)
    state = hnsw_init(cfg)
    levels = jnp.asarray(sample_levels(40, cfg))
    state, n_ins = hnsw_insert_batch(cfg, state, vecs, pcs, levels,
                                     jnp.ones(40, bool))
    assert int(state.count) == 16    # stops at capacity...
    assert int(n_ins) == 16          # ...and the caller can see 24 dropped


def test_empty_index_search():
    cfg = HNSWConfig(capacity=16, words=32, M=4, M0=8, ef_construction=8,
                     ef_search=8, max_level=2)
    state = hnsw_init(cfg)
    q = jnp.zeros((3, 32), jnp.uint32)
    ids, sims = hnsw_search(cfg, state, q, k=4)
    assert (np.asarray(ids) == -1).all()
    assert np.isneginf(np.asarray(sims)).all()


@pytest.mark.parametrize("metric", ["bitmap_jaccard", "minhash_jaccard"])
def test_packed_visited_bitset_equivalence(metric):
    """The packed uint32 visited bitset is a pure representation change:
    construction produces the identical graph and search returns
    bit-identical (ids, sims) vs the historical bool mask, per metric."""
    sigs = _corpus(300, dup_rate=0.35)
    cfg, state, vecs = _build(sigs, metric)          # packed (default)
    assert cfg.packed_visited
    cfgb = cfg._replace(packed_visited=False)
    _, stateb, _ = _build(sigs, metric, packed_visited=False)
    for a, b in zip(state, stateb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    ids_p, sims_p = hnsw_search(cfg, state, vecs, k=4)
    ids_b, sims_b = hnsw_search(cfgb, state, vecs, k=4)
    np.testing.assert_array_equal(np.asarray(ids_p), np.asarray(ids_b))
    np.testing.assert_array_equal(np.asarray(sims_p), np.asarray(sims_b))


def test_query_chunk_equivalence():
    """Chunked execution (now the default) never changes results: explicit
    chunk sizes, the auto default, and the unchunked path all agree."""
    sigs = _corpus(300)
    cfg, state, vecs = _build(sigs)
    ids0, sims0 = hnsw_search(cfg, state, vecs, k=4, query_chunk=0)
    for chunk in (None, 64, 100, 256):    # None = capacity-derived default
        ids, sims = hnsw_search(cfg, state, vecs, k=4, query_chunk=chunk)
        np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids0))
        np.testing.assert_array_equal(np.asarray(sims), np.asarray(sims0))


def test_ef_smaller_than_k_still_returns_k_columns():
    """Regression: ef < k used to return fewer than k columns, breaking
    downstream (B, k) shape assumptions; ef is clamped to max(ef, k)."""
    sigs = _corpus(120)
    cfg, state, vecs = _build(sigs)
    ids, sims = hnsw_search(cfg, state, vecs[:16], k=8, ef=2)
    assert ids.shape == (16, 8) and sims.shape == (16, 8)
    ids_ref, sims_ref = hnsw_search(cfg, state, vecs[:16], k=8, ef=8)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids_ref))
    np.testing.assert_array_equal(np.asarray(sims), np.asarray(sims_ref))


def test_insert_batch_reports_inserted_count():
    """n_inserted tracks the mask when there is room and stops counting at
    capacity — the overflow signal the index backends refuse on."""
    sigs = _corpus(60)
    vecs = pack_bitmaps(jnp.asarray(sigs), T=1024)
    pcs = popcount(vecs)
    cfg = HNSWConfig(capacity=40, words=32, M=4, M0=8, ef_construction=8,
                     ef_search=8, max_level=2)
    state = hnsw_init(cfg)
    levels = jnp.asarray(sample_levels(60, cfg))
    mask = np.ones(60, bool)
    mask[1::3] = False                          # 40 True rows: exactly fits
    state, n = hnsw_insert_batch(cfg, state, vecs, pcs, levels,
                                 jnp.asarray(mask))
    assert int(n) == int(mask.sum()) == int(state.count) == 40
    # a second batch has no room at all
    state, n2 = hnsw_insert_batch(cfg, state, vecs, pcs, levels,
                                  jnp.ones(60, bool))
    assert int(n2) == 0 and int(state.count) == 40


def test_adjacency_invariants():
    sigs = _corpus(300)
    cfg, state, _ = _build(sigs)
    nbrs = np.asarray(state.neighbors)
    count = int(state.count)
    # neighbor ids are either -1 or valid inserted nodes, never self-loops
    for lev in range(nbrs.shape[0]):
        for node in range(0, count, 29):
            row = nbrs[lev, node]
            valid = row[row >= 0]
            assert (valid < count).all()
            assert (valid != node).all()


# ---------------------------------------------- batched insert equivalence
def _states_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(a, b))


@pytest.mark.parametrize("heuristic,levels_kind", [
    (False, "sampled"), (True, "sampled"), (False, "tied"),
])
def test_batched_single_row_equals_sequential(heuristic, levels_kind):
    """Property sweep: driving the batched two-phase path one row at a time
    produces a graph BIT-IDENTICAL to the per-doc fori path over the whole
    batch (phase A degenerates to the sequential search; phase B replays
    the same prune/link/entry updates). Covers mask permutations (random
    skip patterns), level-tie orderings (all rows forced to one level),
    and the diversity heuristic."""
    sigs = _corpus(48, dup_rate=0.4)
    vecs = pack_bitmaps(jnp.asarray(sigs), T=1024)
    pcs = popcount(vecs)
    cfg = HNSWConfig(capacity=96, words=vecs.shape[1], M=8, M0=16,
                     ef_construction=16, ef_search=16, max_level=3,
                     select_heuristic=heuristic)
    if levels_kind == "tied":
        levels = jnp.ones(48, jnp.int32)     # every row ties on level 1
    else:
        levels = jnp.asarray(sample_levels(48, cfg))
    mask = RNG.random(48) < 0.7

    seq_cfg = cfg._replace(batched_insert=False)
    st_seq, n_seq = hnsw_insert_batch(seq_cfg, hnsw_init(seq_cfg), vecs, pcs,
                                      levels, jnp.asarray(mask))
    st_one = hnsw_init(cfg)
    n_tot = 0
    for i in range(48):
        st_one, n = hnsw_insert_batch(cfg, st_one, vecs[i:i + 1],
                                      pcs[i:i + 1], levels[i:i + 1],
                                      jnp.asarray(mask[i:i + 1]))
        n_tot += int(n)
    assert n_tot == int(n_seq) == int(mask.sum())
    assert _states_equal(st_seq, st_one)


def test_batched_insert_recall_parity():
    """AC: the two-phase batched commit (seeded from a prior search, the
    production reuse_search configuration) builds a graph whose recall vs
    brute force is at most 0.01 below the per-doc path on a seeded
    duplicate-dense corpus (one-sided: scoring higher is fine)."""
    sigs = _corpus(400, dup_rate=0.35)
    vecs = pack_bitmaps(jnp.asarray(sigs), T=2048)
    pcs = popcount(vecs)
    cfg = HNSWConfig(capacity=1024, words=vecs.shape[1], M=12, M0=24,
                     ef_construction=40, ef_search=40, max_level=3)
    levels = jnp.asarray(sample_levels(400, cfg))

    def recall(c, st):
        ids, _ = hnsw_search(c, st, vecs, k=4)
        full = np.asarray(pairwise_bitmap_jaccard(vecs, vecs))
        gt = np.argsort(-full, axis=1)[:, :4]
        return np.mean([len(set(gt[i]) & set(np.asarray(ids[i]))) / 4
                        for i in range(400)])

    # online protocol: search-then-insert per batch, seeds from the search
    st_b = hnsw_init(cfg)
    for s in range(0, 400, 100):
        sl = slice(s, s + 100)
        seed_ids, _ = hnsw_search(cfg, st_b, vecs[sl], k=4)
        st_b, _ = hnsw_insert_batch(cfg, st_b, vecs[sl], pcs[sl], levels[sl],
                                    jnp.ones(100, bool), seed_ids=seed_ids)
    seq_cfg = cfg._replace(batched_insert=False)
    st_s = hnsw_init(seq_cfg)
    for s in range(0, 400, 100):
        sl = slice(s, s + 100)
        st_s, _ = hnsw_insert_batch(seq_cfg, st_s, vecs[sl], pcs[sl],
                                    levels[sl], jnp.ones(100, bool))
    rec_b, rec_s = recall(cfg, st_b), recall(seq_cfg, st_s)
    assert rec_b >= rec_s - 0.01, (rec_b, rec_s)

    # seeded construction keeps the structural invariants
    nbrs = np.asarray(st_b.neighbors)
    count = int(st_b.count)
    for lev in range(nbrs.shape[0]):
        for node in range(0, count, 37):
            row = nbrs[lev, node]
            valid = row[row >= 0]
            assert (valid < count).all() and (valid != node).all()


@pytest.mark.parametrize("batched", [True, False])
def test_overflow_mid_batch_parity(batched):
    """Overflow interaction: both insert organizations admit exactly the
    rows that fit (in batch order), report the same n_inserted, and leave
    slots past capacity untouched."""
    sigs = _corpus(40)
    vecs = pack_bitmaps(jnp.asarray(sigs), T=1024)
    pcs = popcount(vecs)
    cfg = HNSWConfig(capacity=16, words=32, M=4, M0=8, ef_construction=8,
                     ef_search=8, max_level=2, batched_insert=batched)
    state = hnsw_init(cfg)
    mask = np.ones(40, bool)
    mask[5] = mask[11] = False          # skipped rows shift who overflows
    levels = jnp.asarray(sample_levels(40, cfg))
    state, n = hnsw_insert_batch(cfg, state, vecs, pcs, levels,
                                 jnp.asarray(mask))
    assert int(n) == 16 == int(state.count)
    lv = np.asarray(state.node_level)
    assert (lv[:16] >= 0).all() and (lv[16:] == -1).all()
    # the 16 admitted rows are the first 16 True rows of the mask
    kept_rows = np.flatnonzero(mask)[:16]
    got = np.asarray(state.vectors[:16])
    exp = np.asarray(vecs)[kept_rows]
    np.testing.assert_array_equal(got, exp)


def test_link_back_honors_select_heuristic():
    """Satellite regression: back-link pruning must apply _select_diverse
    when cfg.select_heuristic is on (hnswlib semantics: heuristic on
    overflow, plain append while the row has room). The old code always
    pruned by plain top-k — this test fails on that behavior."""
    cfg = HNSWConfig(capacity=8, words=1, M=2, M0=2, ef_construction=4,
                     ef_search=4, max_level=1, metric="hamming",
                     select_heuristic=True)
    state = hnsw_init(cfg)
    vecs = np.zeros((8, 1), np.uint32)
    vecs[1, 0] = 0b1          # d(1, v0)=1 bit
    vecs[2, 0] = 0b11         # d(2, v0)=2 bits, but d(2, v1)=1 -> not diverse
    vecs[3, 0] = 0b11100      # d(3, v0)=3 bits,     d(3, v1)=4 -> diverse
    state = state._replace(
        vectors=jnp.asarray(vecs),
        node_level=jnp.where(jnp.arange(8) < 4, 0, -1),
        count=jnp.int32(4),
        neighbors=state.neighbors.at[0, 0].set(jnp.array([1, 2], jnp.int32)))

    # overfull row {1,2} + new node 3: heuristic keeps the diverse {1,3};
    # plain top-k (the old behavior, and select_heuristic=False) keeps {1,2}
    sel = jnp.array([0, -1], jnp.int32)
    row_h = np.asarray(_link_back(cfg, state, jnp.int32(3), 0, sel,
                                  2).neighbors[0, 0])
    assert set(row_h.tolist()) == {1, 3}, row_h
    row_t = np.asarray(_link_back(cfg._replace(select_heuristic=False),
                                  state, jnp.int32(3), 0, sel,
                                  2).neighbors[0, 0])
    assert set(row_t.tolist()) == {1, 2}, row_t

    # room in the row: hnswlib appends WITHOUT consulting the heuristic,
    # even when the newcomer is not diverse (node 2 vs selected node 1)
    state_room = state._replace(
        neighbors=state.neighbors.at[0, 0].set(jnp.array([1, -1], jnp.int32)))
    row_r = np.asarray(_link_back(cfg, state_room, jnp.int32(2), 0, sel,
                                  2).neighbors[0, 0])
    assert set(row_r.tolist()) == {1, 2}, row_r


# ------------------------------------------- commit: linking pairs only
def _commit_reference(cfg, state, levels, admit, slots, fwd, sel):
    """The commit as a branch-free lax.scan over every row and every level:
    an inactive (row, level) pair runs _link_back in full and has only its
    writes dropped."""
    def body(st, xs):
        slot, adm, level, f_row, s_row = xs
        top = st.top_level               # frozen for this row's insert
        for lev in range(cfg.max_level, -1, -1):   # static unroll
            m_l = cfg.M0 if lev == 0 else cfg.M
            active = adm & (lev <= jnp.minimum(level, top))
            slot_w = jnp.where(active, slot, cfg.capacity)   # OOB -> no-op
            st = st._replace(neighbors=st.neighbors
                             .at[lev, slot_w].set(f_row[lev], mode="drop"))
            st = _link_back(cfg, st, slot, lev,
                            jnp.where(active, s_row[lev, :m_l], -1), m_l)
        higher = adm & (level > top)
        return st._replace(
            entry=jnp.where(higher, slot, st.entry),
            top_level=jnp.where(adm, jnp.maximum(top, level), top)), None

    state, _ = jax.lax.scan(body, state, (slots, admit, levels, fwd, sel))
    return state


def _insert_with_commit(commit):
    """hnsw_insert_batch as a program of its own whose phase B is `commit`.
    A new function object over patched globals, so that no trace of the
    production program is shared with it."""
    f = hnsw_insert_batch.__wrapped__
    g = types.FunctionType(f.__code__,
                           dict(f.__globals__, _commit_batch=commit),
                           f.__name__, f.__defaults__, f.__closure__)
    g.__kwdefaults__ = f.__kwdefaults__
    return jax.jit(g, static_argnames=("cfg",))


def _delete_and_compact(cfg, state, ids):
    state, _ = hnsw_delete(cfg, state, ids)
    state, _ = hnsw_compact(cfg, state)
    return state


@pytest.mark.parametrize("heuristic", [False, True])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_commit_links_only_active_pairs_bit_identical(seed, heuristic):
    """The commit's per-level loops over the linking rows build the graph
    the full 5-level scan over every row builds, bit for bit, on the same
    forward rows and back-link targets: batches into an empty index (the
    first row links nowhere), masked rows, rows at levels 1..3 and rows
    that raise the top mid-batch, and slots reused from the free list."""
    rng = np.random.default_rng(100 + seed)
    B, n_batches = 32, 5
    sigs = _corpus(B * n_batches, dup_rate=0.3)
    vecs = pack_bitmaps(jnp.asarray(sigs), T=1024)
    pcs = popcount(vecs)
    cfg = HNSWConfig(capacity=256, words=vecs.shape[1], M=4, M0=8,
                     ef_construction=16, ef_search=16, max_level=3,
                     select_heuristic=heuristic)
    ref_insert = _insert_with_commit(_commit_reference)

    # levels grow batch by batch so that rows raise the top mid-batch
    caps = [1, 2, 3, 3, 3]
    st_new, st_ref = hnsw_init(cfg), hnsw_init(cfg)
    raised = 0
    for b in range(n_batches):
        sl = slice(b * B, (b + 1) * B)
        levels = np.where(rng.random(B) < 0.25,
                          rng.integers(1, caps[b] + 1, B), 0).astype(np.int32)
        levels[B // 2] = caps[b]
        mask = rng.random(B) < 0.6
        mask[B // 2] = True
        free = None
        if b == n_batches - 1:
            # tombstone and compact a few nodes: the last batch drains them
            ids = jnp.asarray(rng.choice(int(st_new.count), 6, replace=False),
                              jnp.int32)
            st_new = _delete_and_compact(cfg, st_new, ids)
            st_ref = _delete_and_compact(cfg, st_ref, ids)
            lv = np.asarray(st_new.node_level)[:int(st_new.count)]
            free_ids = np.flatnonzero(lv < 0)
            assert len(free_ids) >= 4
            free = jnp.asarray(np.concatenate(
                [free_ids, -np.ones(8 - len(free_ids) % 8, np.int64)]),
                jnp.int32)
        top0 = int(st_new.top_level)
        raised += int(np.any(mask & (levels > top0)))
        st_ref, n_ref = ref_insert(cfg, st_ref, vecs[sl], pcs[sl],
                                   jnp.asarray(levels), jnp.asarray(mask),
                                   free_slots=free)
        st_new, n_new = hnsw_insert_batch(cfg, st_new, vecs[sl], pcs[sl],
                                          jnp.asarray(levels),
                                          jnp.asarray(mask), free_slots=free)
        assert int(n_new) == int(n_ref) == int(mask.sum())
        for name in ("neighbors", "entry", "top_level", "count",
                     "node_level", "vectors"):
            np.testing.assert_array_equal(
                np.asarray(getattr(st_new, name)),
                np.asarray(getattr(st_ref, name)), err_msg=f"{name} batch {b}")
    assert raised >= 3
    assert int(st_new.top_level) == 3
