"""The one traffic generator. A mix file under bench/mixes/ is data only: it
names an arrival process and its parameters, and no mix has code of its
own. A new mix that these keys can express is a new data file.

arrival "closed" (a bulk re-ingest): submit `chunk_docs` consecutive stream
documents whenever the service's backlog is below `backlog_docs`, and poll
otherwise. The stream is sized for `stream_docs_per_s` over the window; a
run that uses it up fails.

arrival "open" (independent clients): `request_docs`-document requests of
consecutive stream documents arrive open-loop. `phases` splits the window
into parts, each a `share` of it offered at its own `rate_docs_per_s`: one
phase is a steady Poisson stream, alternating ones are bursts. A phase's
number of requests is fixed, rate x its seconds / request_docs, and so is
the set of gaps between them: the exponential distribution's quantiles at
(i + 1/2) / n, scaled to fill the phase. The seed only orders them, so
every seed offers the same work and the same burstiness, in another order.
The service is polled every `poll_ms` between arrivals. A request is timed
from its scheduled arrival.

Keys for every arrival process:
  refetch_share  share of the window's documents that are exact re-submits
                 of an earlier document of the stream (a crawler fetching a
                 page again); the positions are a fixed number, drawn from
                 the seed (default 0)
  warm           "prefill": the prefill's full batches are the only shapes
                 the mix emits; "all_buckets": every (B, L) of the
                 service's menu is emitted once before the window
  trace_seconds  length of the profiler trace inside the window

Every request is recorded as (scheduled time, first doc id, end doc id,
submit time) on the perf_counter clock.
"""
from __future__ import annotations

import time
from typing import Callable

import numpy as np

__all__ = ["StreamExhausted", "Stream", "window_docs", "warm_docs", "warm",
           "refetch", "drive"]


class StreamExhausted(RuntimeError):
    pass


class Stream:
    """The run's documents in submission order; doc id = row."""

    def __init__(self, tokens: np.ndarray, lengths: np.ndarray):
        self.tokens = tokens
        self.lengths = lengths
        self.cursor = 0

    def take(self, n: int) -> tuple[int, int]:
        if self.cursor + n > len(self.lengths):
            raise StreamExhausted(
                f"the stream's {len(self.lengths)} documents are used up; "
                f"the run never reuses documents")
        s = self.cursor
        self.cursor += n
        return s, self.cursor


def _phases(mix: dict, seconds: float) -> list[tuple[float, int]]:
    """(seconds, requests) of each phase of an open-loop mix."""
    out = []
    for ph in mix["phases"]:
        secs = ph["share"] * seconds
        out.append((secs, max(1, round(ph["rate_docs_per_s"] * secs
                                       / mix["request_docs"]))))
    return out


def window_docs(mix: dict, seconds: float) -> int:
    """Documents the stream must hold for the window."""
    if mix["arrival"] == "closed":
        return int(mix["stream_docs_per_s"] * seconds) + mix["chunk_docs"]
    if mix["arrival"] == "open":
        return sum(n for _, n in _phases(mix, seconds)) * mix["request_docs"]
    raise ValueError(f"unknown arrival process {mix['arrival']!r}")


def warm_docs(svc, mix: dict) -> int:
    """Documents `warm` takes from the stream."""
    if mix["warm"] == "prefill":
        return 0
    if mix["warm"] == "all_buckets":
        return len(svc.batcher.len_buckets) * sum(svc.batcher.batch_buckets)
    raise ValueError(f"unknown warm-up {mix['warm']!r}")


def warm(svc, stream: Stream, mix: dict) -> list[tuple[int, int]]:
    """Emit the shapes the mix can emit that the prefill did not: with
    "all_buckets", every (B, L) of the service's menu once, from stream
    documents cut to L tokens."""
    shapes: list[tuple[int, int]] = []
    if warm_docs(svc, mix) == 0:
        return shapes
    for L in svc.batcher.len_buckets:
        for B in svc.batcher.batch_buckets:
            s, e = stream.take(B)
            stream.tokens[s:e, L:] = 0
            np.minimum(stream.lengths[s:e], L, out=stream.lengths[s:e])
            svc.submit(stream.tokens[s:e], stream.lengths[s:e])
            svc.flush()
            shapes.append((B, L))
    return shapes


def refetch(stream: Stream, mix: dict, seed: int, first: int) -> int:
    """Turn the mix's `refetch_share` of documents first.. into exact copies
    of earlier stream documents; returns how many."""
    n = len(stream.lengths) - first
    k = round(mix.get("refetch_share", 0.0) * n)
    if k == 0:
        return 0
    rng = np.random.default_rng([seed, 2])
    at = np.sort(rng.choice(n, size=k, replace=False)) + first
    for i in at:
        j = int(rng.integers(0, i))
        stream.tokens[i] = stream.tokens[j]
        stream.lengths[i] = stream.lengths[j]
    return k


def _arrivals(mix: dict, seed: int, w0: float, seconds: float
              ) -> np.ndarray:
    rng = np.random.default_rng([seed, 1])
    out, at = [], w0
    for secs, n in _phases(mix, seconds):
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
        gaps = rng.permutation(gaps) * (secs / gaps.sum())
        out.append(at + np.concatenate([[0.0], np.cumsum(gaps)[:-1]]))
        at += secs
    return np.concatenate(out)


def drive(svc, stream: Stream, mix: dict, seed: int, w0: float,
          seconds: float, spans, tick: Callable[[float], None]
          ) -> list[tuple[float, int, int, float]]:
    """Offer the mix's load from w0 until w0 + seconds; returns requests."""
    clock = time.perf_counter
    end = w0 + seconds
    reqs: list[tuple[float, int, int, float]] = []

    def submit(sched: float, n: int) -> None:
        s, e = stream.take(n)
        t = clock()
        with spans.span("submit"):
            ticket = svc.submit(stream.tokens[s:e], stream.lengths[s:e])
        if tuple(ticket) != (s, e):
            raise RuntimeError(f"ticket {tuple(ticket)} is not docs {s}..{e}")
        reqs.append((sched, s, e, t))

    if mix["arrival"] == "closed":
        while True:
            now = clock()
            tick(now)
            if now >= end:
                return reqs
            if svc.backlog() < mix["backlog_docs"]:
                submit(now, mix["chunk_docs"])
            else:
                with spans.span("poll"):
                    svc.poll()

    poll_s = mix["poll_ms"] / 1e3
    next_poll = 0.0
    for sched in list(_arrivals(mix, seed, w0, seconds)) + [end]:
        while True:
            now = clock()
            tick(now)
            if now >= sched:
                break
            if now >= next_poll:
                with spans.span("poll"):
                    svc.poll()
                next_poll = now + poll_s
            else:
                with spans.span("wait"):
                    time.sleep(min(sched, next_poll) - now)
        if sched < end:
            submit(float(sched), mix["request_docs"])
    return reqs
