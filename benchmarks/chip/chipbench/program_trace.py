"""The program's own marks in a profiler trace, beside ``tracing``'s.

The program writes host spans named ``seizure.*`` (``repro.obs``) with
numeric args, and names its device stages with ``jax.named_scope``
(``mspca``, ``eigh``, ``wpd``, ``vote``, ``ring``; ``featurize``,
``moments``, ``rotate``, ``grow``, ``gather``).

``load`` reads the newest ``.xplane.pb`` as ``tracing.load`` does, and
keeps the program's spans too, each with its numeric args: host events
are ``[name, start_ns, dur_ns, {arg: number}]``. The chip's operation
events carry no scope (on the TPU v5e under JAX 0.9 an ``XLA Ops``
event has the instruction's short name and no ``op_name``), so
``attach_scopes`` gives each operation that ran inside an execution of a
program the ``op_name`` metadata of the instruction of the same name in
that program's compiled HLO text: ``[name, start_ns, dur_ns, path]``.
The text has to be that of the executable that ran: a persistent
compilation cache hit from a commit without the scopes brings back its
own metadata (JAX leaves metadata out of the cache key unless
``jax_compilation_cache_include_metadata_in_key`` is set).

``reduce`` gives ``tracing.reduce``'s ``Reduction`` of the same events,
unchanged, and a ``Program``: span seconds, counts and summed args in
the window; each scope segment's device seconds on the first chip (the
union of the intervals of the operations whose path holds it, clipped to
the window: a ``while`` and its body overlap); and its idle gaps cut at
the edges of the host spans, each piece named by the innermost span that
covers it (``tracing``'s rule, program spans included), adjacent pieces
of one name merged, so that the idle time between two engine steps is
put down to ``seizure.assemble``, ``seizure.put`` and the others, not to
the ``bench.poll`` around them. Three-element events
(``testdata/trace_small.json``) reduce too.
"""

from __future__ import annotations

import bisect
import functools
import glob
import os
import re
from typing import NamedTuple

from chipbench import tracing

PROGRAM_PREFIX = "seizure."
_WRAPPED = re.compile(r"^[\w.]+\((.*)\)$")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%([\w.-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.-]+)")


def load(trace_dir: str) -> dict:
    """``tracing.load``'s events, with the program's spans and every host
    span's numeric args (see the module docstring)."""
    import jax

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    host: list = []
    devices: dict = {}
    for plane in data.planes:
        if tracing.DEVICE_PLANE.match(plane.name):
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key:
                    dev[key].extend([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)]
                                    for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    [ev.name, int(ev.start_ns), int(ev.duration_ns),
                     {k: v for k, v in ev.stats
                      if isinstance(v, (int, float))}]
                    for ev in line.events
                    if ev.name.startswith(("bench.", PROGRAM_PREFIX)))
    return {"host": host, "devices": devices}


def hlo_paths(hlo_text: str) -> dict:
    """Instruction name -> ``op_name`` in a compiled module's HLO text. An
    instruction without one (XLA drops it on some fusions, such as the
    grower's scatter histograms) takes the scopes that all the
    instructions of the computations it calls (``calls=``) share."""
    comps: dict = {}
    body: list = []
    for line in hlo_text.splitlines():
        if (m := _COMPUTATION.match(line)):
            body = comps.setdefault(m.group(1), [])
        elif (m := _INSTRUCTION.match(line)):
            op = _OP_NAME.search(line)
            body.append((m.group(1), op.group(1) if op else None,
                         _CALLS.findall(line)))
    memo: dict = {}

    def scopes(comp: str) -> set:
        """The scope parts (path without the primitive) in ``comp``."""
        if comp not in memo:
            memo[comp] = set()
            found = set()
            for _, op, calls in comps.get(comp, []):
                if op is not None:
                    found.add(tuple(op.split("/")[:-1]))
                for c in calls:
                    found |= scopes(c)
            memo[comp] = found
        return memo[comp]

    paths: dict = {}
    for instructions in comps.values():
        for name, op, calls in instructions:
            if op is not None:
                paths[name] = op
                continue
            found = set().union(*(scopes(c) for c in calls))
            if found:
                shared = []
                for parts in zip(*found):
                    if len(set(parts)) > 1:
                        break
                    shared.append(parts[0])
                paths[name] = "/".join(shared + [name])
    return paths


def attach_scopes(events: dict, hlo_text: str) -> int:
    """Give each operation that ran inside an execution of the program
    whose compiled HLO is ``hlo_text`` (``HloModule <name>``) the
    ``op_name`` of its instruction there; every other operation keeps
    the path it has, or gets an empty one. Returns how many operations
    were given a path."""
    module = re.match(r"HloModule ([\w.-]+)", hlo_text).group(1)
    paths = hlo_paths(hlo_text)
    given = 0
    for dev in events["devices"].values():
        runs = sorted((s, s + d) for n, s, d in dev["modules"]
                      if module in n)
        starts = [s for s, _ in runs]
        for op in dev["ops"]:
            name, start = tracing.short_name(op[0]), op[1]
            i = bisect.bisect_right(starts, start) - 1
            if i >= 0 and start < runs[i][1] and name in paths:
                op[3:] = [paths[name]]
                given += 1
            elif len(op) < 4:
                op.append("")
    return given


@functools.cache
def segments(path: str) -> frozenset:
    """The scope names on an operation's path, its last part (the
    primitive) left out and transform wrappers peeled:
    ``jit(f)/vmap(mspca)/jit(denoise)/eigh/jit(eigh)/eigh`` -> {"f",
    "mspca", "denoise", "eigh"}; a bare ``gather`` -> {}. Cached: a
    window repeats a few thousand paths millions of times."""
    out = set()
    for part in path.split("/")[:-1]:
        while (m := _WRAPPED.match(part)):
            part = m.group(1)
        if part:
            out.add(part)
    return frozenset(out)


class Program(NamedTuple):
    span_s: dict                   # seizure.* span -> seconds in the window
    span_count: dict               # seizure.* span -> occurrences
    span_args: dict                # seizure.* span -> {arg: summed value}
    scope_s: dict                  # first device -> {scope segment: seconds}
    gaps: list                     # [(name, seconds)] on the first device,
                                   # cut at host span edges

    def first_scope_s(self) -> dict:
        return self.scope_s[tracing._first(self.scope_s)]

    def span(self, name: str) -> float:
        """Seconds of the span ``name`` in the window; raises where the
        window has none (the mark was renamed or never ran)."""
        if name not in self.span_s:
            raise RuntimeError(f"no {name} span in the traced window: the "
                               f"program's mark was renamed or never ran")
        return self.span_s[name]

    def arg(self, span: str, name: str) -> float:
        """The summed arg ``name`` of the span ``span`` (0 where the span
        has no such arg); raises as ``span`` does."""
        self.span(span)
        return self.span_args[span].get(name, 0)

    def scope(self, name: str) -> float:
        """Device seconds of the scope segment ``name`` on the first chip;
        raises where no operation in the window carries it."""
        seconds = self.first_scope_s().get(name)
        if seconds is None:
            raise RuntimeError(f"no operation of scope {name!r} in the "
                               f"traced window: the scope was renamed, never "
                               f"ran, or the program's HLO lost it")
        return seconds


def scope_ms_per_execution(ctx: dict, scope: str, program: str):
    """A reader's value: device milliseconds of ``scope`` per execution of
    the jitted ``program`` in the window; None untraced; raises where the
    trace has no execution of ``program`` or no operation of ``scope``."""
    prog, trace = ctx.get("program"), ctx.get("trace")
    if prog is None or trace is None:
        return None
    _, calls = trace.program(program)
    if not calls:
        raise RuntimeError(f"no execution of {program} in the trace: the "
                           f"program was renamed or not run")
    return prog.scope(scope) * 1e3 / calls


def _scope_seconds(ops, lo: int, hi: int) -> dict:
    by_segment: dict = {}
    for _, start, dur, *path in ops:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s and path:
            for seg in segments(path[0]):
                by_segment.setdefault(seg, []).append((s, e))
    return {seg: sum(e - s for s, e in tracing._union(iv)) / 1e9
            for seg, iv in by_segment.items()}


def _name_idle(busy, host, lo: int, hi: int) -> list[tuple[str, float]]:
    cuts = sorted({t for _, s, e in host for t in (s, e)})
    edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
    out: list = []
    for s, e in zip(edges[::2], edges[1::2]):
        if e <= s:
            continue
        inner = cuts[bisect.bisect_right(cuts, s):bisect.bisect_left(cuts, e)]
        points = [s] + inner + [e]
        pieces: list = []
        for a, b in zip(points, points[1:]):
            (name, sec), = tracing._name_gaps([], host, a, b)
            if pieces and pieces[-1][0] == name:
                pieces[-1] = (name, pieces[-1][1] + sec)
            else:
                pieces.append((name, sec))
        out += pieces
    return out


def reduce(events: dict) -> tuple[tracing.Reduction, Program]:
    plain_host = [h[:3] for h in events["host"]]
    # What tracing.load keeps: the benchmark's spans alone.
    plain = {"host": [h for h in plain_host
                      if not h[0].startswith(PROGRAM_PREFIX)],
             "devices": {d: {"ops": [o[:3] for o in dev["ops"]],
                             "modules": dev["modules"]}
                         for d, dev in events["devices"].items()}}
    red = tracing.reduce(plain)
    lo, hi = [(s, s + d) for n, s, d in plain_host
              if n == tracing.WINDOW_SPAN][0]
    span_s: dict = {}
    span_count: dict = {}
    span_args: dict = {}
    for name, start, dur, *rest in events["host"]:
        s, e = max(start, lo), min(start + dur, hi)
        if e <= s or not name.startswith(PROGRAM_PREFIX):
            continue
        span_s[name] = span_s.get(name, 0.0) + (e - s) / 1e9
        span_count[name] = span_count.get(name, 0) + 1
        summed = span_args.setdefault(name, {})
        for k, v in (rest[0] if rest else {}).items():
            summed[k] = summed.get(k, 0) + v
    # The first chip's only: every reader reads it, and a four-chip
    # window holds millions of operations.
    first = tracing._first(red.devices)
    scope_s = {first: _scope_seconds(events["devices"][first]["ops"], lo, hi)}
    host = [(n, s, e) for n, s, e in tracing._clip(plain_host, lo, hi)
            if n != tracing.WINDOW_SPAN]
    gaps = _name_idle(red.first.busy, host, lo, hi)
    return red, Program(span_s, span_count, span_args, scope_s, gaps)
