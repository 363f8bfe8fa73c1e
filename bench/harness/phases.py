"""Phase times inside the device programs, and the service's own host spans,
from the profiler trace that `harness.trace` reads.

`load` reads all that `harness.trace.load` reads, and besides:

    ops    per device, the self time of every "XLA Ops" event, each piece
           with the program's `jax.named_scope` names on its op path
           ("fold.insert.discover", ...), outermost first
    spans  the service's own host spans ("fold.dispatch.step", ...), each
           with its arguments (the micro-batch's `batch` id, ...)
    held   the host's waits inside the runtime's launch of a program: the
           self time of PJRT's "ExecutePrepare", where a launch waits for
           a free slot of the device's execution queue (32 deep on the
           v5e) until an earlier program ends

An op's self time is its duration less the op events nested in it (a
while loop's event holds its body's ops); it counts for every `fold.`
scope on its path, so a scope's time holds the scopes nested in it. Per
batch, as `harness.reduce` does for whole programs: clipped to the
window's whole dispatch intervals and divided by their number.

Host time counts as the host's own work inside the service's spans and
outside its waits: "fold.collect.wait", "fold.sync.*" and `held`.

The readers under bench/metrics/ that use these find nothing in a `Trace`
of `harness.trace.load` and read None.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import os
import re

from harness.reduce import Context
from harness.trace import Event, Trace, load as load_trace, union_ns

__all__ = ["PhaseTrace", "Span", "load", "scope_ms_per_batch",
           "host_dispatch_ms", "host_busy_ms", "idle_gaps"]

Piece = tuple[tuple[str, ...], int, int]     # fold scopes, start ns, ns
Span = tuple[str, int, int, dict]            # name, start ns, ns, args

_SCOPE = re.compile(r"fold\.[\w.]+")
WAITS = ("fold.collect.wait", "fold.sync.")
LAUNCH = "CommonPjRtLoadedExecutable::ExecutePrepare"


@dataclasses.dataclass
class PhaseTrace(Trace):
    ops: list[list[Piece]] = dataclasses.field(default_factory=list)
    spans: list[Span] = dataclasses.field(default_factory=list)
    held: list[Event] = dataclasses.field(default_factory=list)


def self_pieces(events: list[tuple[tuple[str, ...], int, int]]
                ) -> list[Piece]:
    """Each event's time that no event nested in it covers."""
    out: list[Piece] = []
    stack: list[list] = []              # [scopes, covered up to, end]

    def close(until: float) -> None:
        while stack and stack[-1][2] <= until:
            scopes, at, end = stack.pop()
            if end > at:
                out.append((scopes, at, end - at))
            if stack:
                stack[-1][1] = max(stack[-1][1], end)

    for scopes, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        close(s)
        if stack and s > stack[-1][1]:
            out.append((stack[-1][0], stack[-1][1], s - stack[-1][1]))
            stack[-1][1] = s
        stack.append([scopes, s, s + d])
    close(float("inf"))
    return out


def xplane_pb2():
    """The XSpace protobuf classes: the generated module that TensorFlow
    installs, loaded alone (TensorFlow itself is not imported). The op
    scopes sit in the events' metadata, which `jax.profiler.ProfileData`
    does not expose."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        raise RuntimeError("reading op scopes needs TensorFlow's xplane_pb2")
    path = os.path.join(spec.submodule_search_locations[0], "tsl",
                        "profiler", "protobuf", "xplane_pb2.py")
    mspec = importlib.util.spec_from_file_location("xplane_pb2", path)
    mod = importlib.util.module_from_spec(mspec)
    mspec.loader.exec_module(mod)
    return mod


def _stat(s):
    kind = s.WhichOneof("value")
    return None if kind is None else getattr(s, kind)


def load(src: str | bytes) -> PhaseTrace:
    """Read an .xplane.pb (a path, or its bytes) into a PhaseTrace."""
    from harness.trace import _is_device
    if not isinstance(src, bytes):
        with open(src, "rb") as f:
            src = f.read()
    base = load_trace(src)
    xs = xplane_pb2().XSpace()
    xs.ParseFromString(src)
    ops, spans, held = [], [], []
    for plane in xs.planes:
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        if _is_device(plane.name):
            scopes = {mid: tuple(_SCOPE.findall(str(_stat(st))))
                      for mid, md in plane.event_metadata.items()
                      for st in md.stats if names.get(st.metadata_id)
                      == "tf_op"}
            ops.append(self_pieces([
                (scopes.get(e.metadata_id, ()),
                 (line.timestamp_ns * 1000 + e.offset_ps) // 1000,
                 e.duration_ps // 1000)
                for line in plane.lines if line.name == "XLA Ops"
                for e in line.events]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = []
                for e in line.events:
                    name = plane.event_metadata[e.metadata_id].name
                    s = (line.timestamp_ns * 1000 + e.offset_ps) // 1000
                    evs.append(((name,), s, e.duration_ps // 1000))
                    if name.startswith("fold."):
                        spans.append((name, s, e.duration_ps // 1000,
                                      {names[st.metadata_id]: _stat(st)
                                       for st in e.stats}))
                if any(n == (LAUNCH,) for n, _, _ in evs):
                    held += [(LAUNCH, s, d) for n, s, d in self_pieces(evs)
                             if n == (LAUNCH,)]
    return PhaseTrace(window=base.window, modules=base.modules,
                      host=base.host, ops=ops, spans=spans, held=held)


def _phases(ctx: Context) -> PhaseTrace | None:
    tr = ctx.trace
    return tr if isinstance(tr, PhaseTrace) else None


def scope_ms_per_batch(ctx: Context, scope: str) -> float | None:
    """Device self time per micro-batch under `scope`; None when the
    window holds under two dispatches or no op ran under the scope."""
    tr, span = _phases(ctx), ctx.dispatch_intervals()
    if tr is None or span is None:
        return None
    window, n = span
    per_dev = []
    for pieces in tr.ops:
        evs = tr.clip([(s, a, d) for s, a, d in pieces if scope in s],
                      window)
        if evs:
            per_dev.append(sum(d for _, _, d in evs) / 1e6)
    if not per_dev:
        return None
    return sum(per_dev) / len(per_dev) / n


def _work_ms(ctx: Context, prefix: str) -> float | None:
    """Host ms per micro-batch inside the service spans named `prefix...`
    and outside every wait."""
    tr, span = _phases(ctx), ctx.dispatch_intervals()
    if tr is None or span is None:
        return None
    window, n = span
    spans = [(k, s, d) for k, s, d, _ in tr.spans if k.startswith(prefix)]
    if not spans:
        return None
    spans = tr.clip(spans, window)
    waits = tr.clip([(k, s, d) for k, s, d, _ in tr.spans
                     if k.startswith(WAITS)] + tr.held, window)
    # |spans - waits| = |spans + waits| - |waits|, each as a union
    return (union_ns(spans + waits) - union_ns(waits)) / 1e6 / n


def host_dispatch_ms(ctx: Context) -> float | None:
    """Host ms per micro-batch of work in the executor's two dispatches
    ("fold.dispatch.*"): shingle, padding upload, level sampling and the
    programs' launch, less the waits inside them."""
    return _work_ms(ctx, "fold.dispatch.")


def host_busy_ms(ctx: Context) -> float | None:
    """Host ms per micro-batch of the service's own work: inside any
    "fold." span and outside every wait."""
    return _work_ms(ctx, "fold.")


def idle_gaps(ctx: Context, top: int = 10) -> list[list]:
    """The `top` longest idle gaps of the first device, each named by the
    innermost benchmark or service span covering its middle."""
    from harness.reduce import _busy, _gaps, _host_at
    devs = ctx.devices()
    tr = _phases(ctx)
    if not devs or tr is None:
        return []
    host = ([("bench." + n, s, d) for n, s, d in tr.host]
            + [(n, s, d) for n, s, d, _ in tr.spans])
    gaps = sorted(_gaps(_busy(ctx, devs[0]), ctx.trace.window),
                  key=lambda g: g[0] - g[1])[:top]
    return [[_host_at(host, (a + b) // 2), (b - a) / 1e9] for a, b in gaps]
