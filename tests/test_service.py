"""The online serving layer: micro-batching, pipelining, index lifecycle."""
import dataclasses
import os

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.bitmap import pack_bitmaps, popcount
from repro.core.dedup import FoldConfig, FoldPipeline
from repro.core.hnsw import (HNSWConfig, hnsw_grow, hnsw_init,
                             hnsw_insert_batch, hnsw_search, sample_levels)
from repro.data.corpus import DATASET_PRESETS, SyntheticCorpus
from repro.service import (DedupService, IndexManager, MicroBatcher,
                           PipelinedExecutor, ServiceConfig)

FC = FoldConfig(capacity=2048, ef_construction=32, ef_search=32,
                threshold_space="minhash")


def _docs(n, seed=0, lo=8, hi=300):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 50_000, rng.integers(lo, hi)).astype(np.uint32)
            for _ in range(n)]


# ------------------------------------------------------------------ batcher
def test_batcher_bucketed_shapes_bounded():
    """Ragged traffic must land on the bucket menu only: the compiled
    program count is |batch_buckets| x |len_buckets| for the lifetime."""
    b = MicroBatcher(max_batch=64, max_wait_ms=0.0,
                     len_buckets=(64, 128, 256), batch_buckets=(16, 32, 64),
                     max_len=256)
    rng = np.random.default_rng(0)
    out = []
    for doc_id, doc in enumerate(_docs(500, lo=1, hi=400)):
        b.add(doc_id, doc)
        if rng.random() < 0.3:
            out.extend(b.drain())
    out.extend(b.drain(force=True))
    assert b.pending == 0
    allowed = {(B, L) for B in (16, 32, 64) for L in (64, 128, 256)}
    assert b.emitted_shapes <= allowed
    # every doc covered exactly once, padding rows marked invalid
    ids = np.concatenate([mb.doc_ids[mb.valid] for mb in out])
    assert sorted(ids.tolist()) == list(range(500))
    for mb in out:
        assert mb.shape in allowed
        assert (mb.lengths[~mb.valid] == 0).all()
        assert (mb.doc_ids[~mb.valid] == -1).all()
        # padding rows come after all real rows (greedy-sweep safety)
        assert mb.valid[: mb.n_docs].all() and not mb.valid[mb.n_docs:].any()
    assert b.truncated > 0      # docs beyond the largest bucket were clipped


def test_batcher_full_batches_emit_without_force():
    b = MicroBatcher(max_batch=8, max_wait_ms=1e9, batch_buckets=(8,))
    for i, d in enumerate(_docs(20)):
        b.add(i, d)
    out = b.drain()
    assert [mb.n_docs for mb in out] == [8, 8]   # remainder of 4 still waits
    assert b.pending == 4
    out = b.drain(force=True)
    assert [mb.n_docs for mb in out] == [4]


# ------------------------------------------------- pipelined == sequential
def test_pipelined_equals_sequential():
    """Same micro-batch partitions through the depth-2 executor and the
    blocking process_batch loop must give bit-identical admit decisions."""
    src = SyntheticCorpus(DATASET_PRESETS["common_crawl"])
    batches = [src.next_batch(96)[:2] for _ in range(4)]

    seq = FoldPipeline(FC)
    keep_seq = np.concatenate(
        [seq.process_batch(t, l)[0] for t, l in batches])

    pipe = FoldPipeline(FC)
    got = []
    ex = PipelinedExecutor(pipe, depth=2,
                           on_outcome=lambda o: got.append(o))
    from repro.service.batcher import MicroBatch
    for t, l in batches:
        B = t.shape[0]
        ex.submit(MicroBatch(tokens=t.astype(np.uint32), lengths=l,
                             valid=np.ones(B, bool),
                             doc_ids=np.arange(B, dtype=np.int64), n_docs=B))
    ex.drain()
    keep_pipe = np.concatenate([o.keep for o in got])
    assert np.array_equal(keep_seq, keep_pipe)
    assert int(seq.state.count) == int(pipe.state.count)


# ----------------------------------------------------------------- growth
def test_hnsw_grow_preserves_search():
    rng = np.random.default_rng(0)
    sigs = rng.integers(0, 2**32, (300, 112), dtype=np.uint32)
    bm = pack_bitmaps(jnp.asarray(sigs), T=4096)
    pcs = popcount(bm)
    cfg = HNSWConfig(capacity=512, words=128, M=8, M0=16,
                     ef_construction=32, ef_search=32, max_level=3)
    st = hnsw_init(cfg)
    st, _ = hnsw_insert_batch(cfg, st, bm, pcs,
                              jnp.asarray(sample_levels(300, cfg)),
                              jnp.ones(300, bool))
    ids0, sims0 = hnsw_search(cfg, st, bm[:64], k=4)
    cfg2, st2 = hnsw_grow(cfg, st, 2048)
    assert cfg2.capacity == 2048 and int(st2.count) == int(st.count)
    ids1, sims1 = hnsw_search(cfg2, st2, bm[:64], k=4)
    np.testing.assert_array_equal(np.asarray(ids0), np.asarray(ids1))
    np.testing.assert_allclose(np.asarray(sims0), np.asarray(sims1))
    # and the grown index keeps accepting inserts past the old capacity
    more = pack_bitmaps(jnp.asarray(
        rng.integers(0, 2**32, (300, 112), dtype=np.uint32)), T=4096)
    st2, _ = hnsw_insert_batch(cfg2, st2, more, popcount(more),
                               jnp.asarray(sample_levels(300, cfg2, seed=1)),
                               jnp.ones(300, bool))
    assert int(st2.count) == 600 > cfg.capacity


def test_service_grows_past_initial_capacity():
    svc = DedupService(ServiceConfig(
        fold=FoldConfig(capacity=128, M=8, M0=16, ef_construction=16,
                        ef_search=16, threshold_space="minhash"),
        max_batch=32, max_wait_ms=0.0, batch_buckets=(32,),
        grow_watermark=0.75, growth_factor=2.0))
    src = SyntheticCorpus(DATASET_PRESETS["lm1b"])   # ~2% dups: fills fast
    tickets = [svc.submit(*src.next_batch(32)[:2]) for _ in range(12)]
    svc.flush()
    n_admitted = sum(v.admitted for t in tickets for v in svc.results(t))
    s = svc.stats()
    assert s["index"]["grow_events"] >= 1
    assert n_admitted == s["index"]["count"] > 128
    assert s["index"]["capacity"] >= 512


def test_growth_headroom_smaller_than_batch():
    """Regression: when (1-watermark)*capacity < max_batch, growth must be
    sized ahead of the incoming batch — otherwise hnsw_insert_batch silently
    drops overflow rows whose verdicts claim 'admitted'."""
    svc = DedupService(ServiceConfig(
        fold=FoldConfig(capacity=256, M=8, M0=16, ef_construction=16,
                        ef_search=16, threshold_space="minhash"),
        max_batch=128, max_wait_ms=0.0, batch_buckets=(128,),
        grow_watermark=0.85, growth_factor=2.0))   # headroom 39 < 128
    src = SyntheticCorpus(DATASET_PRESETS["lm1b"])  # ~2% dups: fills fast
    tickets = [svc.submit(*src.next_batch(128)[:2]) for _ in range(4)]
    svc.flush()
    admitted = sum(v.admitted for t in tickets for v in svc.results(t))
    s = svc.stats()
    # every admitted verdict is truly in the index, past the initial 256
    assert admitted == s["index"]["count"] > 256
    assert s["index"]["grow_events"] >= 1


# -------------------------------------------------------------- snapshots
def test_snapshot_rotation_roundtrip(tmp_path):
    src = SyntheticCorpus(DATASET_PRESETS["common_crawl"])
    b1, b2, b3 = (src.next_batch(96)[:2] for _ in range(3))

    pipe = FoldPipeline(FC)
    mgr = IndexManager(pipe, snapshot_dir=str(tmp_path), snapshot_every=1,
                       max_snapshots=2)
    pipe.process_batch(*b1)
    mgr.after_batch()                       # snapshot 1
    pipe.process_batch(*b2)
    mgr.after_batch()                       # snapshot 2
    pipe.process_batch(*b3)
    mgr.after_batch()                       # snapshot 3 -> 1 rotated out
    mgr.wait_snapshots()                    # periodic writes are async
    steps = sorted(os.listdir(tmp_path))
    assert steps == ["step_00000002", "step_00000003"]
    keep4_ref, _ = pipe.process_batch(*b1)  # replay: all dups

    pipe2 = FoldPipeline(FC)
    mgr2 = IndexManager(pipe2, snapshot_dir=str(tmp_path))
    assert mgr2.restore_latest() == 3
    # the replay admitted nothing, so the live index still matches snap 3
    assert pipe2.inserted == pipe.inserted
    keep4, _ = pipe2.process_batch(*b1)
    assert np.array_equal(keep4, keep4_ref)


def test_snapshot_restore_after_grow(tmp_path):
    """A snapshot taken post-growth restores into a fresh (small) pipeline."""
    pipe = FoldPipeline(FoldConfig(capacity=128, M=8, M0=16,
                                   ef_construction=16, ef_search=16,
                                   threshold_space="minhash"))
    src = SyntheticCorpus(DATASET_PRESETS["lm1b"])
    b1 = src.next_batch(100)[:2]
    pipe.process_batch(*b1)
    pipe.grow(512)
    b2 = src.next_batch(100)[:2]
    pipe.process_batch(*b2)
    pipe.save(str(tmp_path), step=1)

    pipe2 = FoldPipeline(FoldConfig(capacity=128, M=8, M0=16,
                                    ef_construction=16, ef_search=16,
                                    threshold_space="minhash"))
    pipe2.restore(str(tmp_path), 1)
    assert pipe2.capacity == 512
    assert pipe2.inserted == pipe.inserted
    keep_ref, _ = pipe.process_batch(*b2)    # replay: all dups
    keep_got, _ = pipe2.process_batch(*b2)
    assert np.array_equal(keep_got, keep_ref)
    assert keep_got.sum() == 0


def test_pow2_buckets_clamped_to_max():
    from repro.service import pow2_buckets
    assert pow2_buckets(32, 512) == (32, 64, 128, 256, 512)
    assert pow2_buckets(32, 300) == (32, 64, 128, 256, 300)
    assert pow2_buckets(32, 16) == (16,)
    # and the batcher honors a non-power-of-two max_len end to end
    b = MicroBatcher(max_batch=8, max_wait_ms=0.0, max_len=300,
                     batch_buckets=(8,))
    b.add(0, np.arange(1000, dtype=np.uint32))
    mb = b.drain(force=True)[0]
    assert mb.shape[1] == 300 and b.truncated == 1


def test_growth_refuses_at_max_capacity_and_tiny_factor():
    """A near-1 growth factor must not spin, and a max_capacity ceiling
    must refuse ingestion rather than silently drop 'admitted' docs."""
    class StubPipe:                          # just the lifecycle surface
        capacity, inserted = 128, 120       # past the 108-doc watermark

        def grow(self, cap):
            self.capacity = cap

    pipe = StubPipe()
    mgr = IndexManager(pipe, grow_watermark=0.85, growth_factor=1.005,
                       max_capacity=160)
    mgr._known_count = pipe.inserted         # as after a prior sync
    assert mgr.maybe_grow(incoming=0)        # +1-per-step loop terminates
    assert 128 < pipe.capacity <= 160        # grew just past the watermark
    pipe.inserted = 155
    with pytest.raises(RuntimeError, match="index full"):
        mgr.maybe_grow(incoming=32)          # 155 + 32 > ceiling: refuse
    assert pipe.capacity == 160              # ...after growing to the cap

    # a PARTIAL clamp must refuse too: growth to 160 cannot fit 120+64
    pipe2 = StubPipe()
    mgr2 = IndexManager(pipe2, grow_watermark=0.85, growth_factor=2.0,
                        max_capacity=160)
    mgr2._known_count = pipe2.inserted
    with pytest.raises(RuntimeError, match="index full"):
        mgr2.maybe_grow(incoming=64)
    assert pipe2.capacity == 160


def test_pump_requeues_batches_on_refusal():
    """When growth is refused mid-pump, un-dispatched docs must return to
    the batcher queue instead of vanishing from their tickets."""
    svc = DedupService(ServiceConfig(
        fold=FoldConfig(capacity=128, M=8, M0=16, ef_construction=16,
                        ef_search=16, threshold_space="minhash"),
        max_batch=64, max_wait_ms=0.0, batch_buckets=(64,),
        grow_watermark=0.85, max_capacity=128))   # growth forbidden
    src = SyntheticCorpus(DATASET_PRESETS["lm1b"])  # ~2% dups: fills fast
    with pytest.raises(RuntimeError, match="index full"):
        for _ in range(4):
            svc.submit(*src.next_batch(64)[:2])
    assert svc.batcher.pending >= 64          # refused batch was requeued
    svc.executor.drain()                      # materialize what did dispatch
    admitted = svc.stats()["counters"].get("admitted", 0)
    assert admitted == svc.backend.inserted <= 128


def test_restore_smaller_snapshot_into_bigger_config(tmp_path):
    """Restoring a snapshot taken at a smaller capacity must rebuild at the
    snapshot's shapes and grow back to the configured capacity."""
    small = FoldConfig(capacity=256, M=8, M0=16, ef_construction=16,
                       ef_search=16, threshold_space="minhash")
    src = SyntheticCorpus(DATASET_PRESETS["common_crawl"])
    b1 = src.next_batch(100)[:2]
    pipe = FoldPipeline(small)
    pipe.process_batch(*b1)
    pipe.save(str(tmp_path), step=1)

    import dataclasses
    pipe2 = FoldPipeline(dataclasses.replace(small, capacity=1024))
    pipe2.restore(str(tmp_path), 1)
    assert pipe2.capacity == 1024           # grown back after the load
    assert pipe2.inserted == pipe.inserted
    assert pipe2.state.vectors.shape[0] == 1024
    keep, _ = pipe2.process_batch(*b1)      # replay: all dups
    assert keep.sum() == 0


def test_snapshot_step_resumes_past_existing(tmp_path):
    """A restarted IndexManager must not clobber committed snapshots."""
    pipe = FoldPipeline(FC)
    mgr = IndexManager(pipe, snapshot_dir=str(tmp_path), max_snapshots=5)
    assert mgr.snapshot() == 1
    assert mgr.snapshot() == 2
    mgr2 = IndexManager(FoldPipeline(FC), snapshot_dir=str(tmp_path),
                        max_snapshots=5)    # fresh process, same dir
    assert mgr2.snapshot() == 3
    assert sorted(os.listdir(tmp_path))[-1] == "step_00000003"


# ------------------------------------------------------------ front API
def test_service_verdicts_and_metrics():
    svc = DedupService(ServiceConfig(
        fold=FC, max_batch=64, max_wait_ms=0.0, batch_buckets=(64,)))
    src = SyntheticCorpus(DATASET_PRESETS["common_crawl"])
    toks, lens, _ = src.next_batch(100)
    t1 = svc.submit(toks, lens)
    t2 = svc.submit(toks, lens)              # exact replay: all duplicates
    v1 = svc.results(t1)
    v2 = svc.results(t2)
    assert [v.doc_id for v in v1] == list(range(100))
    assert sum(v.admitted for v in v1) > 0
    assert sum(v.admitted for v in v2) == 0
    # replayed docs must cite a real neighbor above the (bitmap-space)
    # duplicate threshold unless dropped inside their own batch
    from repro.core.dedup import bitmap_tau
    for v in v2:
        assert v.reason in ("batch_dup", "index_dup")
        if v.reason == "index_dup":
            assert v.neighbor_id >= 0 and v.similarity >= bitmap_tau(FC)
    s = svc.stats()
    assert s["counters"]["docs_in"] == s["counters"]["docs_out"] == 200
    assert s["counters"]["admitted"] == s["index"]["count"]
    assert s["latency_ms"]["batch_ms"]["n"] >= 2
    assert s["qps"] > 0
    # results() pops: asking again for a consumed ticket raises
    with pytest.raises(KeyError):
        svc.results(t1)


def test_service_backed_ingest():
    """DedupIngest's service mode filters the same way the direct mode
    reports: admitted rows flow to the packer, totals line up."""
    from repro.data.ingest import DedupIngest
    src = SyntheticCorpus(DATASET_PRESETS["common_crawl"])
    svc = DedupService(ServiceConfig(
        fold=FC, max_batch=64, max_wait_ms=0.0, batch_buckets=(64,)))
    ing = DedupIngest(src, service=svc)
    for _ in range(3):
        toks, lens, stats = ing.next_clean_batch(100)
        assert toks.shape[0] == lens.shape[0] == stats["n_insert"]
    assert ing.total_in == 300
    assert ing.total_admitted == svc.backend.inserted
    assert svc.stats()["counters"]["docs_out"] == 300


def test_sharded_backend_masked_step():
    """The multi-shard fused backend honors padding masks and replay
    through the generic pipeline surface (multi-device behaviour of the
    underlying step is covered in test_dist.py::test_sharded_dedup_8dev)."""
    from repro.index import make_pipeline
    cfg = FoldConfig(capacity=512, M=8, M0=16, ef_construction=16,
                     ef_search=16, threshold_space="minhash")
    pipe = make_pipeline("hnsw_sharded", cfg=cfg)  # 1 CPU device -> 1 shard
    src = SyntheticCorpus(DATASET_PRESETS["common_crawl"])
    toks, lens, _ = src.next_batch(50)
    sig = pipe.signatures(toks, lens)
    valid = np.ones(50, bool)
    valid[45:] = False
    r1 = pipe.dedup_step(sig, valid=valid)
    r2 = pipe.dedup_step(sig, valid=valid)   # replay: all dups
    k1, k2 = np.asarray(r1.keep), np.asarray(r2.keep)
    assert k1.sum() > 0 and not k1[45:].any()
    assert k2.sum() == 0
    assert pipe.inserted == k1.sum() <= pipe.capacity


def test_service_reports_stage_timers_with_batched_insert():
    """AC: the reuse_search batched insert is exercised end-to-end through
    DedupService and the sampled Fig. 7 stage breakdown (t_insert included)
    lands in stats(); verdicts keep the replay-duplicate property."""
    svc = DedupService(ServiceConfig(
        fold=FC, max_batch=64, max_wait_ms=0.0, batch_buckets=(64,),
        stage_timer_every=1))             # time every batch for the test
    assert svc.pipeline.backend.hnsw_cfg.batched_insert   # production default
    assert svc.pipeline.backend.cfg.reuse_search
    src = SyntheticCorpus(DATASET_PRESETS["common_crawl"])
    toks, lens, _ = src.next_batch(64)
    t1 = svc.submit(toks, lens)
    t2 = svc.submit(toks, lens)           # exact replay: all duplicates
    assert sum(v.admitted for v in svc.results(t1)) > 0
    assert sum(v.admitted for v in svc.results(t2)) == 0
    lat = svc.stats()["latency_ms"]
    # batch 0 (the XLA-compile batch) is deliberately never sampled, so
    # only the second batch lands in the stage histograms here
    for key in ("t_in_batch_ms", "t_search_ms", "t_insert_ms"):
        assert lat[key]["n"] >= 1, (key, lat.keys())
        assert lat[key]["mean"] >= 0.0


def test_service_single_doc_requests():
    """One-doc submits coalesce; verdicts still come back per ticket."""
    svc = DedupService(ServiceConfig(
        fold=FC, max_batch=16, max_wait_ms=1e9, batch_buckets=(16,)))
    docs = _docs(12, seed=3)
    tickets = [svc.submit([d]) for d in docs]
    # 12 < max_batch and nothing is overdue: everything still coalescing
    assert svc.executor.inflight == 0 and svc.batcher.pending == 12
    svc.flush()
    verdicts = [svc.results(t)[0] for t in tickets]
    assert len({v.doc_id for v in verdicts}) == 12
    # 12 docs bucket up to B=16 with 4 masked padding rows
    assert svc.stats()["batching"]["compiled_shapes"] == [(16, 512)]


@pytest.mark.parametrize("max_level", [0, 4])
def test_service_counts_commit_links(max_level):
    """stats() reports commit_links, one observation a batch: the (row,
    level) pairs the insert's commit linked, levels 0..level of each kept
    row under the levels the backend sampled. With every row at level 0 it
    is the batch's kept count."""
    fold = dataclasses.replace(FC, max_level=max_level)
    svc = DedupService(ServiceConfig(
        fold=fold, max_batch=64, max_wait_ms=0.0, batch_buckets=(64,),
        stage_timer_every=0))
    src = SyntheticCorpus(DATASET_PRESETS["common_crawl"])
    toks, lens, _ = src.next_batch(192)
    ticket = svc.submit(toks, lens)
    kept = np.array([v.admitted for v in svc.results(ticket)]).reshape(3, 64)
    hcfg = svc.pipeline.backend.hnsw_cfg
    want = [int(np.sum(sample_levels(64, hcfg, seed=b + fold.seed + 1)
                       [kept[b]] + 1)) for b in range(3)]
    if max_level == 0:
        assert want == kept.sum(axis=1).tolist()
    else:
        assert sum(want) > kept.sum()        # some kept row is above level 0
    assert 0 < kept.sum() < kept.size
    links = svc.stats()["latency_ms"]["commit_links"]
    assert links["n"] == 3
    assert links["max"] == max(want)
    assert svc.metrics.histograms["commit_links"].sum == sum(want)
