"""Profiler capture inside the window, and its reduction to plain events.

A traced run starts JAX's profiler a little after the window opens and
stops it a few seconds later. The capture is read back with
`jax.profiler.ProfileData` (nothing but JAX) into a `Trace`: for each
device its program executions ("XLA Modules"), and the benchmark's own
host spans, all on the profiler's one clock. The
traced window is the host span "bench.window", opened right after the
profiler starts and closed right before it stops.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import shutil
import tempfile
import time

import jax

__all__ = ["Capture", "Trace", "load", "union_ns"]

Event = tuple[str, int, int]           # name, start ns, duration ns


@dataclasses.dataclass
class Trace:
    window: tuple[int, int]                    # start, end ns
    modules: list[list[Event]]                 # per device
    host: list[Event]                          # bench.* spans, prefix cut

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def clip(self, events: list[Event],
             window: tuple[int, int] | None = None) -> list[Event]:
        """The events cut to `window` (default: the traced window)."""
        a, b = window or self.window
        out = []
        for name, s, d in events:
            lo, hi = max(s, a), min(s + d, b)
            if hi > lo:
                out.append((name, lo, hi - lo))
        return out


def union_ns(events: list[Event]) -> int:
    """Length of the union of the events' intervals."""
    total, end = 0, None
    for _, s, d in sorted(events, key=lambda e: e[1]):
        e = s + d
        if end is None or s >= end:
            total += d
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


class Capture:
    """start() / stop() the profiler into a private temporary directory."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self._window = None
        self.started = self.stopped = False

    def start(self) -> None:
        jax.profiler.start_trace(self.dir)
        self._window = jax.profiler.TraceAnnotation("bench.window")
        self._window.__enter__()
        self.t0 = time.perf_counter()
        self.started = True

    def stop(self) -> None:
        self.t1 = time.perf_counter()
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.stopped = True

    def path(self) -> str:
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {self.dir}")
        return found[0]

    def close(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") and "CPU" not in plane_name \
        and "NON_CORE" not in plane_name.upper()


def load(src: str | bytes) -> Trace:
    """Read an .xplane.pb (a path, or its bytes) into a Trace."""
    from jax.profiler import ProfileData
    pd = (ProfileData.from_serialized_xspace(src) if isinstance(src, bytes)
          else ProfileData.from_file(src))
    modules, host = [], []
    for plane in pd.planes:
        if _is_device(plane.name):
            modules.append([(e.name, int(e.start_ns), int(e.duration_ns))
                            for line in plane.lines
                            if line.name == "XLA Modules"
                            for e in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append((e.name[len("bench."):],
                                     int(e.start_ns), int(e.duration_ns)))
    win = [e for e in host if e[0] == "window"]
    if not win:
        raise ValueError("the trace has no bench.window span")
    _, s, d = win[0]
    host = [e for e in host if e[0] != "window"]
    return Trace(window=(s, s + d), modules=modules, host=host)
