"""The trace reduction on a trace recorded on the chip: a 2.35 s window of
`cc_ingest_max` on one TPU v5 lite (16 micro-batches, recorded with the
index at M=16, M0=32, ef 64), cut down to the device's program executions
and the benchmark's host spans."""
from __future__ import annotations

import gzip
import os

import pytest

from harness import reduce as red
from harness.spec import load_cell
from harness.trace import load
from tiny import ROOT

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "cc_ingest_max.xplane.pb.gz")


@pytest.fixture(scope="module")
def raw():
    with gzip.open(DATA) as f:
        return f.read()


@pytest.fixture(scope="module")
def ctx(raw):
    return red.Context(trace=load(raw), batches=[], mix={}, config={})


def _plain(raw):
    """Window, program events and the window's dedup_step starts, read
    straight from the profile."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(raw)
    window, progs, steps = None, [], []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == "bench.window":
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                elif line.name == "XLA Modules":
                    progs.append((e.name, e.start_ns, e.duration_ns))
    for plane in pd.planes:
        for line in plane.lines:
            steps += [e.start_ns for e in line.events
                      if e.name == "bench.dedup_step"
                      and window[0] <= e.start_ns < window[1]]
    return window, progs, sorted(steps)


def test_window_and_batches(raw, ctx):
    window, _, steps = _plain(raw)
    assert ctx.trace.window == (int(window[0]), int(window[1]))
    assert ctx.n_batches == len(steps) == 16
    assert ctx.devices() == [0]


def test_layer_times_are_clipped_program_sums(raw, ctx):
    """Per batch: program time between the first and the last dispatch in
    the window, over the dispatch intervals between them."""
    _, progs, steps = _plain(raw)
    a, b, n = steps[0], steps[-1], len(steps) - 1
    assert ctx.dispatch_intervals() == ((a, b), n)
    for name in ("hnsw_insert_batch", "hnsw_search", "_greedy_sweep"):
        want = sum(max(0.0, min(s + d, b) - max(s, a))
                   for p, s, d in progs if name in p) / 1e6 / n
        got = red.module_ms_per_batch(ctx, (name,))
        assert got == pytest.approx(want, rel=1e-9)
    assert red.module_ms_per_batch(ctx, ("no_such_program",)) is None
    # the device's busy time over those intervals is about one batch's
    # work each: the layers account for the interval's length
    layers = sum(red.module_ms_per_batch(ctx, (p,)) for p in
                 ("hnsw_insert_batch", "hnsw_search", "_greedy_sweep",
                  "minhash_kernel_signatures", "pack_bitmaps",
                  "bitmap_jaccard_matrix"))
    assert 0.8 * (b - a) / 1e6 / n < layers <= (b - a) / 1e6 / n


def test_under_two_dispatches_reads_nothing(raw):
    tr = load(raw)
    tr.host = [e for e in tr.host if e[0] != "dedup_step"]
    c = red.Context(trace=tr, batches=[], mix={}, config={})
    assert c.dispatch_intervals() is None
    assert red.module_ms_per_batch(c, ("hnsw_search",)) is None


def test_idle_share_is_one_minus_busy_union(raw, ctx):
    (a, b), progs, _ = _plain(raw)
    edges = sorted((max(s, a), min(s + d, b)) for _, s, d in progs
                   if s + d > a and s < b)
    busy, end = 0.0, a
    for lo, hi in edges:
        if hi > end:
            busy += hi - max(lo, end)
            end = hi
    assert red.busy_s(ctx) == pytest.approx(busy / 1e9, rel=1e-9)
    idle = red.idle_share(ctx)
    assert idle == pytest.approx(100 * (1 - busy / (b - a)), rel=1e-9)
    assert 0 < idle < 100


def test_every_per_layer_reader_of_the_cell(ctx):
    cell = load_cell("cc_ingest_max", ROOT)
    got = {e["name"]: r(ctx) for e, r in cell.per_layer}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["insert_device_ms.ingest"] > got["search_device_ms.ingest"]


def test_breakdown(ctx):
    bd = red.breakdown(ctx)
    assert 0 < len(bd["device_ops"]) <= 10 and 0 < len(bd["idle_gaps"]) <= 10
    assert bd["device_ops"][0][0] == "jit_hnsw_insert_batch"
    secs = [s for _, s in bd["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert all(isinstance(n, str) and s > 0 for n, s in bd["idle_gaps"])
