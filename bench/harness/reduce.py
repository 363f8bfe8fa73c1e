"""Reductions from a traced window to per-layer numbers.

Device times per micro-batch are sums of program executions ("XLA
Modules" events) whose name holds one of a layer's program names, clipped
to whole dispatch intervals: from the first to the last start of a
"dedup_step" host span inside the traced window, divided by the intervals
between them. With the pipeline full, each interval holds one batch's
device work, whatever the phase of the window against the batches. Busy
time is the union of the device's program executions over the whole
traced window; idle share is 1 - busy / window. Both are means over the
devices the run used. The per-layer
metric files under bench/metrics/ call these with their own names.
"""
from __future__ import annotations

import dataclasses
import re

from harness.trace import Event, Trace, union_ns

__all__ = ["Context", "module_ms_per_batch", "busy_s", "idle_share",
           "breakdown", "program_name"]

_SUFFIX = re.compile(r"\(\d+\)$")


def program_name(event_name: str) -> str:
    """'jit_hnsw_search(17)' -> 'jit_hnsw_search'."""
    return _SUFFIX.sub("", event_name)


@dataclasses.dataclass
class Context:
    """What a per-layer reader may read."""
    trace: Trace
    batches: list                 # window batch records
    mix: dict
    config: dict

    def host_spans(self, names: tuple[str, ...]) -> list[Event]:
        """Benchmark host spans named in `names` that start in the window."""
        a, b = self.trace.window
        return [e for e in self.trace.host if e[0] in names and a <= e[1] < b]

    @property
    def n_batches(self) -> int:
        """Micro-batches dispatched inside the traced window."""
        return len(self.host_spans(("dedup_step",)))

    def dispatch_intervals(self) -> tuple[tuple[int, int], int] | None:
        """(first, last) start of the window's dedup_step spans and the
        number of dispatch intervals between them; None under two."""
        starts = sorted(s for _, s, _ in self.host_spans(("dedup_step",)))
        if len(starts) < 2:
            return None
        return (starts[0], starts[-1]), len(starts) - 1

    def devices(self) -> list[int]:
        return [i for i, mods in enumerate(self.trace.modules) if mods]


def _matches(name: str, programs: tuple[str, ...]) -> bool:
    return any(p in name for p in programs)


def module_ms_per_batch(ctx: Context, programs: tuple[str, ...]
                        ) -> float | None:
    """Device ms per micro-batch in the programs named; None when the
    window holds under two dispatches or no such program ran."""
    span = ctx.dispatch_intervals()
    if span is None:
        return None
    window, n = span
    per_dev = []
    for i in ctx.devices():
        evs = ctx.trace.clip([e for e in ctx.trace.modules[i]
                              if _matches(e[0], programs)], window)
        if evs:
            per_dev.append(sum(d for _, _, d in evs) / 1e6)
    if not per_dev:
        return None
    return sum(per_dev) / len(per_dev) / n


def _busy(ctx: Context, i: int) -> list[Event]:
    return ctx.trace.clip(ctx.trace.modules[i])


def busy_s(ctx: Context) -> float | None:
    devs = ctx.devices()
    if not devs:
        return None
    return sum(union_ns(_busy(ctx, i)) for i in devs) / len(devs) / 1e9


def idle_share(ctx: Context) -> float | None:
    """Idle percent of the traced window (mean over devices)."""
    b = busy_s(ctx)
    if b is None:
        return None
    return 100.0 * (1.0 - b / ctx.trace.window_s)


def _gaps(busy: list[Event], window: tuple[int, int]) -> list[tuple[int, int]]:
    out, at = [], window[0]
    for _, s, d in sorted(busy, key=lambda e: e[1]):
        if s > at:
            out.append((at, s))
        at = max(at, s + d)
    if window[1] > at:
        out.append((at, window[1]))
    return out


def _host_at(host: list[Event], t: int) -> str:
    """The innermost benchmark span covering time t."""
    best = None
    for name, s, d in host:
        if s <= t < s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "outside_spans"


def breakdown(ctx: Context) -> dict:
    """The device programs that took most time (seconds, summed over the
    run's devices) and the longest idle gaps of the first device, each
    named by the host span it falls in."""
    totals: dict[str, int] = {}
    for i in ctx.devices():
        for name, _, d in ctx.trace.clip(ctx.trace.modules[i]):
            key = program_name(name)
            totals[key] = totals.get(key, 0) + d
    ops = sorted(totals.items(), key=lambda kv: -kv[1])[:10]
    devs = ctx.devices()
    gaps = []
    if devs:
        gaps = sorted(_gaps(_busy(ctx, devs[0]), ctx.trace.window),
                      key=lambda g: g[0] - g[1])[:10]
    return {"device_ops": [[k, v / 1e9] for k, v in ops],
            "idle_gaps": [[_host_at(ctx.trace.host, (a + b) // 2),
                           (b - a) / 1e9] for a, b in gaps]}
