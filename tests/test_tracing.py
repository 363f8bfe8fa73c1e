"""The profiler's names for the hot programs' phases and the service's host
path. The benchmark's phase readers find device time by these scope names
and host time by these span names, so a renamed scope or span would empty
a metric without failing anything else."""
import dataclasses
import glob
import re
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.core.dedup import FoldConfig
from repro.core.hnsw import (HNSWConfig, abstract_state, hnsw_insert_batch,
                             hnsw_search)
from repro.data.corpus import DATASET_PRESETS, SyntheticCorpus
from repro.service import DedupService, ServiceConfig

CFG = HNSWConfig(capacity=64, words=4, M=4, M0=8, ef_construction=8,
                 ef_search=8, max_level=2, select_heuristic=True)
B = 4


def _op_names(compiled) -> list[list[str]]:
    """The fold.* scopes on each op's path, outermost first (a transform
    wraps the name it applies to, as in "vmap(fold.search.beam)")."""
    return [re.findall(r"fold\.[\w.]+", n) for n in
            re.findall(r'op_name="([^"]*)"', compiled.as_text())]


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _search_ops():
    return _op_names(hnsw_search.lower(
        CFG, abstract_state(CFG), _sds((B, CFG.words), jnp.uint32),
        k=2).compile())


def _insert_ops():
    row = lambda d: _sds((B,), d)    # noqa: E731
    return _op_names(hnsw_insert_batch.lower(
        CFG, abstract_state(CFG), _sds((B, CFG.words), jnp.uint32),
        row(jnp.int32), row(jnp.int32), row(jnp.bool_),
        _sds((B, 2), jnp.int32), None).compile())


@pytest.mark.parametrize("program, scopes", [
    (_search_ops, ("fold.search.descend", "fold.search.beam")),
    (_insert_ops, ("fold.insert.discover", "fold.insert.merge",
                   "fold.insert.commit", "fold.select_diverse")),
])
def test_scopes_reach_compiled_op_names(program, scopes):
    paths = program()
    for scope in scopes:
        assert any(scope in p for p in paths), scope


def test_select_diverse_nests_in_merge_and_commit():
    """The selection heuristic's time is part of merge and of commit."""
    paths = _insert_ops()
    for outer in ("fold.insert.merge", "fold.insert.commit"):
        assert any(outer in p and "fold.select_diverse" in p
                   and p.index(outer) < p.index("fold.select_diverse")
                   for p in paths), outer
    # the phases are disjoint: no op sits under two of them
    phases = {"fold.insert.discover", "fold.insert.merge",
              "fold.insert.commit"}
    assert all(len(phases & set(p)) <= 1 for p in paths)


# ------------------------------------------------------------- host spans
def _service(**kw) -> DedupService:
    fold = FoldConfig(capacity=512, M=8, M0=16, ef_construction=16,
                      ef_search=16, threshold_space="minhash")
    return DedupService(ServiceConfig(
        fold=fold, max_batch=8, max_wait_ms=1e9, batch_buckets=(8,),
        len_buckets=(64,), max_len=64, **kw))


def _docs(n, seed):
    toks, lens, _ = SyntheticCorpus(dataclasses.replace(
        DATASET_PRESETS["common_crawl"], mean_len=48, max_len=64, seed=seed)
    ).next_batch(n)
    return toks, lens


def _trace_spans(fn) -> list[tuple]:
    """Host spans (name, start, end, args) recorded while fn() runs."""
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    fn()
    jax.profiler.stop_trace()
    pd = ProfileData.from_file(glob.glob(d + "/**/*.xplane.pb",
                                         recursive=True)[0])
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(("fold.", "test."))]


def _profiled(svc: DedupService, n_docs: int) -> list[tuple]:
    """Host spans of one submit and flush, after a first one compiled."""
    svc.submit(*_docs(16, seed=1))
    svc.flush()
    toks, lens = _docs(n_docs, seed=2)
    return _trace_spans(lambda: (svc.submit(toks, lens), svc.flush()))


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.fixture(scope="module")
def spans():
    def hook(out):
        with TraceAnnotation("test.hook"):
            pass

    svc = _service(pipeline_depth=2, stage_timer_every=0)
    svc.outcome_hooks.append(hook)
    return _profiled(svc, 32), svc


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def test_host_spans_nest_as_specified(spans):
    spans, _ = spans
    submit = _named(spans, "fold.submit")
    assert len(submit) == 1 and submit[0][3]["docs"] == 32
    batches = _named(spans, "fold.batch")
    assert len(batches) == 4
    assert all(b[3]["B"] == 8 and b[3]["L"] == 64 for b in batches)
    assert all(_inside(b, submit[0]) for b in batches)
    for outer, inner in [("fold.dispatch.signatures",
                          ("fold.signatures.shingle",)),
                         ("fold.dispatch.step",
                          ("fold.step.in_batch", "fold.step.search",
                           "fold.step.insert"))]:
        outs = _named(spans, outer)
        assert len(outs) == 4
        for name in inner:
            ins = _named(spans, name)
            assert len(ins) == 4
            assert all(any(_inside(i, o) for o in outs) for i in ins), name
    # each stage of the step follows the one before
    steps = [sorted(_named(spans, n), key=lambda s: s[1]) for n in
             ("fold.step.in_batch", "fold.step.search", "fold.step.insert")]
    for a, b, c in zip(*steps):
        assert a[2] <= b[1] and b[2] <= c[1]


def test_one_micro_batch_shares_its_batch_id(spans):
    spans, _ = spans
    ids = {}
    for name in ("fold.dispatch.signatures", "fold.dispatch.step",
                 "fold.collect.wait", "fold.record"):
        ids[name] = sorted(s[3]["batch"] for s in _named(spans, name))
    assert len(set(ids["fold.dispatch.step"])) == 4
    assert all(v == ids["fold.dispatch.step"] for v in ids.values()), ids
    for seq in ids["fold.record"]:
        wait = [s for s in _named(spans, "fold.collect.wait")
                if s[3]["batch"] == seq][0]
        rec = [s for s in _named(spans, "fold.record")
               if s[3]["batch"] == seq][0]
        step = [s for s in _named(spans, "fold.dispatch.step")
                if s[3]["batch"] == seq][0]
        assert step[2] <= wait[1] and wait[2] <= rec[1]
    # outcome hooks run after the service's own record, outside its span
    hooks = _named(spans, "test.hook")
    assert len(hooks) == 4
    assert not any(_inside(h, r) for h in hooks
                   for r in _named(spans, "fold.record"))


def test_host_times_land_in_latency_histograms(spans):
    _, svc = spans
    lat = svc.stats()["latency_ms"]
    n = svc.stats()["counters"]["batches_dispatched"]
    for key in ("dispatch_ms", "collect_wait_ms", "record_ms"):
        assert lat[key]["n"] == n, key
        assert lat[key]["max"] >= lat[key]["p50"] >= 0.0


def test_timed_batch_waits_for_the_work_before_it():
    """A sampled stage-timer batch first waits for what is in flight, so its
    t_* times hold its own work alone; its blocking dispatch is kept out of
    dispatch_ms."""
    svc = _service(pipeline_depth=2, stage_timer_every=3)
    spans = _profiled(svc, 32)               # batches 2..5; 3 is timed
    syncs = _named(spans, "fold.sync.timers")
    assert [s[3]["batch"] for s in syncs] == [3]
    step = [s for s in _named(spans, "fold.dispatch.step")
            if s[3]["batch"] == 3][0]
    assert syncs[0][2] <= step[1]
    lat = svc.stats()["latency_ms"]
    assert lat["dispatch_ms"]["n"] == 6 - 1
    assert lat["t_insert_ms"]["n"] == 1
    assert np.isfinite(lat["t_insert_ms"]["mean"])


def test_growth_and_its_syncs_are_named():
    """The growth check's occupancy sync and the growth itself get spans
    inside the submit that caused them; the overflow guard's re-anchor
    gets one when a standalone pipeline nears capacity."""
    fold = FoldConfig(capacity=16, M=4, M0=8, ef_construction=8,
                      ef_search=8, threshold_space="minhash")
    svc = DedupService(ServiceConfig(
        fold=fold, max_batch=8, max_wait_ms=1e9, batch_buckets=(8,),
        len_buckets=(64,), max_len=64, stage_timer_every=0))
    toks, lens = _docs(32, seed=3)
    spans = _trace_spans(lambda: (svc.submit(toks, lens), svc.flush()))
    submit = _named(spans, "fold.submit")[0]
    grows = _named(spans, "fold.grow")
    assert grows and all(_inside(g, submit) for g in grows)
    assert grows[0][3]["capacity"] > 16
    assert _named(spans, "fold.sync.occupancy")

    from repro.index import make_pipeline
    pipe = make_pipeline("hnsw", cfg=fold)
    t, n = _docs(8, seed=4)

    def fill():
        for _ in range(3):
            pipe.dedup_step(pipe.signatures(t, n))
    spans = _trace_spans(fill)
    assert _named(spans, "fold.sync.capacity")
