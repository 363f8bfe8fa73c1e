"""The phase readers on a trace recorded on the chip: `cc_ingest_max` on one
TPU v5 lite at the configuration's published parameters, made with

    python3 bench/record_phases.py --workload cc_ingest_max \
        --seed 2147484151 --seconds 30 \
        --keep cc_ingest_max_phases.xplane.pb.gz

which cuts the traced window to two dispatch intervals: the device's
"XLA Modules" and "XLA Ops" lines, and on the host the "bench." and
"fold." spans and the runtime's program launches with what they hold."""
from __future__ import annotations

import gzip
import os
import re

import pytest

from harness import phases
from harness import reduce as red
from harness.spec import _load_reader, load_cell
from harness.trace import load
from record_phases import PARTS, PHASE_METRICS, cut
from tiny import ROOT

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
METRICS = os.path.join(ROOT, "bench", "metrics")


def _raw(name: str) -> bytes:
    with gzip.open(os.path.join(DATA, name)) as f:
        return f.read()


@pytest.fixture(scope="module")
def raw():
    return _raw("cc_ingest_max_phases.xplane.pb.gz")


@pytest.fixture(scope="module")
def ctx(raw):
    return red.Context(trace=phases.load(raw), batches=[], mix={}, config={})


def _reader(name: str):
    return _load_reader(os.path.join(METRICS, name + ".py"), name)


def _xspace(raw):
    xs = phases.xplane_pb2().XSpace()
    xs.ParseFromString(raw)
    return xs


def _clip(s, d, a, b) -> int:
    return max(0, min(s + d, b) - max(s, a))


def _device_ops(raw):
    """(tf_op path, start ns, ns) of every "XLA Ops" event, read straight
    from the protobuf."""
    for pl in _xspace(raw).planes:
        if pl.name == "/device:TPU:0":
            names = {k: v.name for k, v in pl.stat_metadata.items()}
            path = {mid: st.str_value for mid, md in pl.event_metadata.items()
                    for st in md.stats if names[st.metadata_id] == "tf_op"}
            line = [ln for ln in pl.lines if ln.name == "XLA Ops"][0]
            return [(path.get(e.metadata_id, ""),
                     (line.timestamp_ns * 1000 + e.offset_ps) // 1000,
                     e.duration_ps // 1000) for e in line.events]
    raise AssertionError("no device plane")


def _under(scope: str, path: str) -> bool:
    """`scope` is a component of the op path, bare or inside a transform's
    name ("vmap(fold.search.beam)")."""
    return re.search(r"(^|[/(])" + re.escape(scope) + r"($|[/)])",
                     path) is not None


def _hand_scope_ms(raw, window, n, scope) -> float:
    """Self time under `scope`, by another route: the trace nests ops one
    deep (a while loop's event holds its body's ops), so an outer op's self
    time is its time less that of the ops inside it."""
    a, b = window
    ops = sorted(_device_ops(raw), key=lambda e: (e[1], -e[2]))
    total, outer, end = 0, None, -1
    for path, s, d in ops:
        inside = s + d <= end
        if inside:
            assert outer is not None
            if _under(scope, outer[0]):
                total -= _clip(s, d, a, b)
        else:
            outer, end = (path, s, d), s + d
        if _under(scope, path):
            total += _clip(s, d, a, b)
    return total / 1e6 / n


def test_ops_nest_one_deep(raw):
    """What the hand computation assumes (events of no length aside)."""
    ops = sorted(_device_ops(raw), key=lambda e: (e[1], -e[2]))
    ends: list[int] = []
    for _, s, d in ops:
        if d == 0:
            continue
        ends = [e for e in ends if e > s]
        assert len(ends) <= 1
        ends.append(s + d)


@pytest.mark.parametrize("metric, scope", [
    ("search_descend_device_ms.ingest", "fold.search.descend"),
    ("search_beam_device_ms.ingest", "fold.search.beam"),
    ("insert_discover_device_ms.ingest", "fold.insert.discover"),
    ("insert_merge_device_ms.ingest", "fold.insert.merge"),
    ("insert_commit_device_ms.ingest", "fold.insert.commit"),
])
def test_scope_reader_is_the_hand_computation(raw, ctx, metric, scope):
    window, n = ctx.dispatch_intervals()
    assert n == 2
    want = _hand_scope_ms(raw, window, n, scope)
    assert want > 0
    assert _reader(metric)(ctx) == pytest.approx(want, rel=1e-9, abs=1e-6)


@pytest.mark.parametrize("whole", sorted(PARTS))
def test_phases_account_for_their_program(ctx, whole):
    """discover + merge + commit is insert's time, descend + beam search's,
    within 2%: what the scopes leave out is a few small ops."""
    parts = sum(_reader(p)(ctx) for p in PARTS[whole])
    assert parts == pytest.approx(_reader(whole)(ctx), rel=0.02)
    assert parts <= _reader(whole)(ctx)


def _host_events(raw):
    """(name, start ns, ns) of the host's fold.* spans and the runtime's
    launch events with the events they hold, straight from the protobuf."""
    out = []
    for pl in _xspace(raw).planes:
        if pl.name.startswith("/host:"):
            for ln in pl.lines:
                for e in ln.events:
                    out.append((pl.event_metadata[e.metadata_id].name,
                                (ln.timestamp_ns * 1000 + e.offset_ps)
                                // 1000, e.duration_ps // 1000, ln.id))
    return out


def _hand_work_ms(raw, window, n, prefix) -> float:
    """Time inside some span named `prefix...` and in no wait, by walking
    the elementary intervals between all event edges."""
    evs = _host_events(raw)
    spans = [(s, s + d) for name, s, d, _ in evs if name.startswith(prefix)]
    waits = [(s, s + d) for name, s, d, _ in evs
             if name.startswith(("fold.collect.wait", "fold.sync."))]
    launches = [e for e in evs if e[0] == phases.LAUNCH]
    for _, s, d, line in launches:          # a launch less what it holds
        held = sorted((cs, cs + cd) for name, cs, cd, cl in evs
                      if cl == line and name != phases.LAUNCH
                      and s <= cs and cs + cd <= s + d)
        at = s
        for lo, hi in held:
            if lo > at:
                waits.append((at, lo))
            at = max(at, hi)
        if s + d > at:
            waits.append((at, s + d))
    a, b = window
    edges = sorted({a, b} | {t for iv in spans + waits for t in iv
                             if a < t < b})
    total = 0
    for lo, hi in zip(edges, edges[1:]):
        if any(s <= lo and hi <= e for s, e in spans) and \
                not any(s <= lo and hi <= e for s, e in waits):
            total += hi - lo
    return total / 1e6 / n


@pytest.mark.parametrize("metric, prefix", [
    ("host_dispatch_ms.ingest", "fold.dispatch."),
    ("host_busy_ms.ingest", "fold."),
])
def test_host_reader_is_the_hand_computation(raw, ctx, metric, prefix):
    window, n = ctx.dispatch_intervals()
    want = _hand_work_ms(raw, window, n, prefix)
    assert 0 < want < 100            # a few ms of a 2 s period
    assert _reader(metric)(ctx) == pytest.approx(want, rel=1e-9)


def test_every_reader_of_the_cell_and_every_phase_reader(ctx):
    """The cell's readers and the phase readers all read on this trace."""
    cell = load_cell("cc_ingest_max", ROOT)
    got = {e["name"]: r(ctx) for e, r in cell.per_layer}
    got.update({n: _reader(n)(ctx) for n in PHASE_METRICS})
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["insert_device_ms.ingest"] > got["search_device_ms.ingest"]


def test_spans_of_a_batch_share_its_id(ctx):
    by = {}
    for name, _, _, args in ctx.trace.spans:
        if name in ("fold.dispatch.signatures", "fold.dispatch.step"):
            by.setdefault(args["batch"], set()).add(name)
    assert len(by) >= 3
    assert all(v == {"fold.dispatch.signatures", "fold.dispatch.step"}
               for v in by.values())


def test_idle_gaps_fall_in_named_spans(ctx):
    gaps = phases.idle_gaps(ctx)
    assert 0 < len(gaps) <= 10
    assert all(name != "outside_spans" and secs > 0 for name, secs in gaps)


def test_cut_keeps_every_reading(raw, ctx):
    steps = sorted(s for n, s, _ in ctx.trace.host if n == "dedup_step")
    again = red.Context(trace=phases.load(cut(raw, steps)), batches=[],
                        mix={}, config={})
    for n in PHASE_METRICS + tuple(PARTS):
        assert _reader(n)(again) == _reader(n)(ctx), n


# ------------------- the program-executions trace (no ops, no fold. spans)
@pytest.fixture(scope="module")
def old_raw():
    return _raw("cc_ingest_max.xplane.pb.gz")


def test_old_readers_read_the_same_through_the_phase_loader(old_raw):
    """Reading with harness.phases changes no existing reader's value."""
    plain = red.Context(trace=load(old_raw), batches=[], mix={}, config={})
    ph = red.Context(trace=phases.load(old_raw), batches=[], mix={},
                     config={})
    for entry, reader in load_cell("cc_ingest_max", ROOT).per_layer:
        assert reader(ph) == reader(plain), entry["name"]
    assert red.breakdown(ph) == red.breakdown(plain)


def test_phase_readers_read_nothing_without_scopes_or_spans(old_raw):
    """A trace of a program without scopes and spans, or one read by
    harness.trace alone, gives no phase reading (and no error)."""
    for tr in (load(old_raw), phases.load(old_raw)):
        c = red.Context(trace=tr, batches=[], mix={}, config={})
        assert all(_reader(n)(c) is None for n in PHASE_METRICS)


def test_self_pieces_nest_at_any_depth():
    a, b, c = ("fold.a",), ("fold.a", "fold.b"), ("fold.c",)
    pieces = phases.self_pieces([(a, 0, 100), (b, 10, 50), (c, 20, 10),
                                 (c, 120, 5)])
    assert sorted(pieces, key=lambda p: p[1]) == [
        (a, 0, 10), (b, 10, 10), (c, 20, 10), (b, 30, 30), (a, 60, 40),
        (c, 120, 5)]
