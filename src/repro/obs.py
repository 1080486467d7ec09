"""Host spans that the program writes into a profiler trace.

``span(name, **args)`` marks one host phase as ``seizure.<name>``, a
``jax.profiler.TraceAnnotation``. The profiler is the only sink: it
keeps the span in memory while a trace runs, puts it on the same clock
as the device's events and writes it out when the trace stops, so an
idle gap of the device can be put down to the host phase that covers
it. Numeric ``args`` (bytes, counts) ride on the span as event stats;
those known only when the phase ends are added with the span's
``set_metadata(**args)``. With no profiler session running, a span
is a ``TraceMe`` that finds none and records nothing, about a
microsecond of host time: there is no flag to turn it off.

The device stages carry ``jax.named_scope`` names instead (``mspca``,
``eigh``, ``dual`` around a Gram-side band, ``wpd``, ``vote``, ``ring``;
in training ``featurize``, ``moments``, ``rotate``, ``grow``,
``gather``). A scope changes only the operations' metadata, never the
compiled program: it is each instruction's ``op_name`` in the
executable's HLO, by which the operations of a trace can be put down
to their stage.
"""

from __future__ import annotations

import jax

PREFIX = "seizure."


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """``with span("fill") as s: ...; s.set_metadata(evictions=2)``."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)
