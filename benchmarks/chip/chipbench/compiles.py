"""Counts compilations and traces while a window runs.

JAX reports each XLA compilation, each program loaded from the
persistent compilation cache and each function trace through
``jax.monitoring``; the counter listens to all three, so a program that
first appears inside the measured window shows however it was served.
"""

from __future__ import annotations

import jax

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"

_counts = {"compiles": 0, "cache_loads": 0, "traces": 0}
_installed = False


def _on_duration(event: str, duration: float, **_) -> None:
    if event == _BACKEND_COMPILE:
        _counts["compiles"] += 1
    elif event == _TRACE:
        _counts["traces"] += 1


def _on_event(event: str, **_) -> None:
    if event == _CACHE_HIT:
        _counts["cache_loads"] += 1


def install() -> None:
    global _installed
    if not _installed:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _installed = True


class Counter:
    """``with Counter() as c: ...`` then ``c.counts``: what compiled,
    loaded from the cache or traced inside the block."""

    def __enter__(self) -> "Counter":
        install()
        self._start = dict(_counts)
        return self

    def __exit__(self, *exc) -> None:
        self.counts = {k: _counts[k] - self._start[k] for k in _counts}

    @property
    def programs(self) -> int:
        return self.counts["compiles"] + self.counts["cache_loads"]
