"""Host spans recorded from the benchmark's side of each layer boundary.

A span is a `jax.profiler.TraceAnnotation` named "bench.<name>": in a
traced run it lands in the profiler's trace on the same clock as the
device's programs, where the reduction counts micro-batches by the
"dedup_step" span and the breakdown names idle gaps by the span they fall
in; otherwise it costs next to nothing. Nothing here
changes the program: the service's pipeline is wrapped on the instance,
and the executor calls it through that instance.
"""
from __future__ import annotations

import contextlib

import jax

__all__ = ["Spans"]


class Spans:
    @contextlib.contextmanager
    def span(self, name: str):
        with jax.profiler.TraceAnnotation("bench." + name):
            yield

    def wrap(self, obj, attr: str, name: str) -> None:
        """Record every call of obj.<attr> as span `name`."""
        inner = getattr(obj, attr)

        def call(*args, **kwargs):
            with self.span(name):
                return inner(*args, **kwargs)

        setattr(obj, attr, call)
