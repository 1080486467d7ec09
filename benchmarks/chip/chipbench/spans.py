"""The benchmark's own host spans and counters.

``span(name)`` times a call into a layer on the host clock and, in a
traced run, writes the same span into the profiler's trace
(``jax.profiler.TraceAnnotation``), where the reduction names the
device's idle gaps by it. ``window()`` marks the measured window.
"""

from __future__ import annotations

import contextlib
import time

import jax


class Recorder:
    def __init__(self, traced: bool):
        self.traced = traced
        self.seconds: dict = {}
        self.calls: dict = {}
        self.counters: dict = {}

    @contextlib.contextmanager
    def span(self, name: str):
        ann = (jax.profiler.TraceAnnotation(name) if self.traced
               else contextlib.nullcontext())
        t0 = time.perf_counter()
        with ann:
            yield
        self.seconds[name] = self.seconds.get(name, 0.0) + (
            time.perf_counter() - t0)
        self.calls[name] = self.calls.get(name, 0) + 1

    def window(self):
        return (jax.profiler.TraceAnnotation("bench.window") if self.traced
                else contextlib.nullcontext())

    def count(self, name: str, n: int = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + n
