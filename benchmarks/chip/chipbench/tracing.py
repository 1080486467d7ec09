"""Reduction of a profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
three kinds of event: the device's operations (line ``XLA Ops``), its
program executions (line ``XLA Modules``), one per device, and the
benchmark's own host spans (``bench.*``, written with
``jax.profiler.TraceAnnotation``). ``reduce`` turns them into busy time,
per-program and collective device time and the idle gaps, all clipped
to the ``bench.window`` span. Both work on plain lists, so a small
recorded trace checks the arithmetic (``testdata/trace_small.json``).
"""

from __future__ import annotations

import glob
import os
import re
from typing import NamedTuple

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
)


def load(trace_dir: str) -> dict:
    """{"host": [[name, start_ns, dur_ns], ...], "devices": {device:
    {"ops": [...], "modules": [...]}}} from the newest xplane file."""
    import jax

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    host: list = []
    devices: dict = {}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key:
                    dev[key].extend([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)]
                                    for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([ev.name, int(ev.start_ns), int(ev.duration_ns)]
                            for ev in line.events
                            if ev.name.startswith("bench."))
    return {"host": host, "devices": devices}


def short_name(name: str) -> str:
    """An operation's name without its HLO text: ``%fusion.3 = f32[..]
    fusion(..)`` -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%") if name.startswith("%") else name


def _clip(events, lo: int, hi: int):
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            yield name, s, e


def _union(intervals) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


class DeviceTime(NamedTuple):
    busy_s: float                  # union of operation intervals
    op_s: dict                     # operation name -> seconds
    module_s: dict                 # program name -> seconds
    module_calls: dict             # program name -> executions
    collective_s: float            # collective operations, summed
    busy: list                     # merged busy intervals (ns)


class Reduction(NamedTuple):
    window_s: float
    devices: dict                  # device plane name -> DeviceTime
    host_s: dict                   # bench.* span name -> seconds
    host_count: dict               # bench.* span name -> occurrences
    gaps: list                     # [(name, seconds)] on the first device

    @property
    def first(self) -> DeviceTime:
        return self.devices[_first(self.devices)]

    def busy_s(self) -> float:
        """Busy seconds averaged over the devices that ran anything."""
        busy = [d.busy_s for d in self.devices.values() if d.busy_s > 0]
        return sum(busy) / len(busy) if busy else 0.0

    def program(self, fragment: str) -> tuple[float, int]:
        """(device seconds, executions) of the programs whose name holds
        ``fragment`` on the first device."""
        dev = self.first
        names = [n for n in dev.module_s if fragment in n]
        return (sum(dev.module_s[n] for n in names),
                sum(dev.module_calls[n] for n in names))

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.first.op_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps, key=lambda g: -g[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def _device_time(dev: dict, lo: int, hi: int) -> DeviceTime:
    ops = list(_clip(dev["ops"] or dev["modules"], lo, hi))
    op_s: dict = {}
    coll = 0.0
    for name, s, e in ops:
        name = short_name(name)
        op_s[name] = op_s.get(name, 0.0) + (e - s) / 1e9
        if COLLECTIVE.search(name):
            coll += (e - s) / 1e9
    module_s: dict = {}
    calls: dict = {}
    for name, s, e in _clip(dev["modules"], lo, hi):
        module_s[name] = module_s.get(name, 0.0) + (e - s) / 1e9
        calls[name] = calls.get(name, 0) + 1
    busy = _union((s, e) for _, s, e in ops)
    return DeviceTime(sum(e - s for s, e in busy) / 1e9, op_s, module_s,
                      calls, coll, busy)


def _name_gaps(busy, host, lo: int, hi: int) -> list[tuple[str, float]]:
    """Each idle interval of the window, named by the host span that
    covers most of it (``host`` = [(name, start, end)])."""
    edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
    gaps = []
    for s, e in zip(edges[::2], edges[1::2]):
        if e <= s:
            continue
        best = ("other", 0, 0)
        for name, hs, he in host:
            o = min(e, he) - max(s, hs)
            # Most overlap wins; between nested spans, the innermost.
            if o > 0 and (o, hs - he) > (best[1], best[2]):
                best = (name, o, hs - he)
        name = best[0]
        gaps.append((name, (e - s) / 1e9))
    return gaps


def _first(devices: dict) -> str:
    """The chip with the lowest id."""
    return min(devices, key=lambda n: int(DEVICE_PLANE.match(n).group(1)))


def reduce(events: dict) -> Reduction:
    windows = [(s, s + d) for n, s, d in events["host"] if n == WINDOW_SPAN]
    if not windows:
        raise RuntimeError(f"the trace holds no {WINDOW_SPAN} span")
    lo, hi = windows[0]
    devices = {name: _device_time(dev, lo, hi)
               for name, dev in events["devices"].items()
               if DEVICE_PLANE.match(name)}
    if not devices:
        raise RuntimeError("the trace holds no device plane")
    host = [(n, s, e) for n, s, e in _clip(events["host"], lo, hi)
            if n != WINDOW_SPAN]
    host_s: dict = {}
    host_count: dict = {}
    for n, s, e in host:
        host_s[n] = host_s.get(n, 0.0) + (e - s) / 1e9
        host_count[n] = host_count.get(n, 0) + 1
    first = devices[_first(devices)]
    return Reduction((hi - lo) / 1e9, devices, host_s, host_count,
                     _name_gaps(first.busy, host, lo, hi))
