"""Reduction of a profiler trace to what the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
three kinds of events, each as ``[name, start_ns, duration_ns]`` on the
trace's one clock:

- ``ops[chip]``: the operations that ran on each TPU chip (the ``XLA Ops``
  line of its ``/device:TPU:<n>`` plane);
- ``modules[chip]``: the executions of whole compiled programs on it (the
  ``XLA Modules`` line), named after the jitted function;
- ``host``: the benchmark's own spans (``bench.*`` trace annotations).

The rest works on that plain form, so that it can be checked on a small
trace written by hand (``test_bench_units.py``).
"""
from __future__ import annotations

import json
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."


def load(path: str | Path) -> dict:
    """The events of the trace at ``path`` in plain form."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    ops: dict[int, list] = {}
    modules: dict[int, list] = {}
    host: list = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, MODULES_LINE):
                dest = (ops if line.name == OPS_LINE else modules
                        ).setdefault(int(m.group(1)), [])
                dest.extend([e.name, e.start_ns, e.duration_ns]
                            for e in line.events)
            elif not m and plane.name.startswith("/host"):
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return {"ops": ops, "modules": modules, "host": host}


def save(trace: dict, path: str | Path) -> None:
    with open(path, "w") as f:
        json.dump(trace, f)


def read(path: str | Path) -> dict:
    """A trace saved by :func:`save` (chip keys back to ints)."""
    with open(path) as f:
        t = json.load(f)
    for k in ("ops", "modules"):
        t[k] = {int(c): ev for c, ev in t[k].items()}
    return t


def merge(intervals) -> list[tuple[float, float]]:
    """Union of ``(start, end)`` intervals as sorted disjoint ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged, lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` that the disjoint ``merged`` intervals
    cover."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def intervals(events) -> list[tuple[float, float]]:
    return [(s, s + d) for _, s, d in events]


def busy(trace: dict, chip: int, lo: float, hi: float) -> float:
    """Nanoseconds of ``[lo, hi]`` in which an operation ran on
    ``chip``."""
    return covered(merge(intervals(trace["ops"].get(chip, []))), lo, hi)


def host_spans(trace: dict, name: str) -> list[tuple[str, float, float]]:
    """Host spans whose name is ``name`` or starts with ``name#``, as
    ``(name, start, end)`` in time order."""
    return sorted(((n, s, s + d) for n, s, d in trace["host"]
                   if n == name or n.startswith(name + "#")),
                  key=lambda x: x[1])


def executions(trace: dict, lo: float, hi: float, pattern: str
               ) -> list[tuple[int, str, float, float]]:
    """Module executions that start in ``[lo, hi]`` and whose name
    contains ``pattern``, as ``(chip, name, start, end)`` in time
    order."""
    out = [(chip, n, s, s + d) for chip, evs in trace["modules"].items()
           for n, s, d in evs if lo <= s <= hi and pattern in n]
    return sorted(out, key=lambda x: x[2])


def op_totals(trace: dict, chips, lo: float, hi: float, top: int = 10
              ) -> list[tuple[str, float]]:
    """The ``top`` operation names by device seconds inside
    ``[lo, hi]``, summed over ``chips``."""
    tot: dict[str, float] = {}
    for c in chips:
        for n, s, d in trace["ops"].get(c, []):
            if lo <= s <= hi:
                tot[n] = tot.get(n, 0.0) + d * 1e-9
    return sorted(tot.items(), key=lambda x: -x[1])[:top]


def idle_gaps(trace: dict, chips, lo: float, hi: float, top: int = 10
              ) -> list[tuple[str, float]]:
    """The ``top`` longest stretches of ``[lo, hi]`` in which a chip ran
    nothing, each named by the chip and by the benchmark's host span that
    covers its middle (``wait`` where none does)."""
    spans = [(n.split("#")[0], s, s + d) for n, s, d in trace["host"]
             if n.split("#")[0] != "bench.window"]
    gaps = []
    for c in chips:
        t = lo
        for s, e in merge(intervals(trace["ops"].get(c, []))) + [(hi, hi)]:
            s, e = max(s, lo), min(e, hi)
            if s > t:
                mid = (t + s) / 2
                # the innermost (shortest) covering span names the gap
                label = min(((b - a, n) for n, a, b in spans
                             if a <= mid <= b), default=(0, "wait"))[1]
                gaps.append((f"chip{c}:{label}", (s - t) * 1e-9))
            t = max(t, e)
    return sorted(gaps, key=lambda x: -x[1])[:top]
