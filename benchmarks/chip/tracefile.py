"""Reduction of a profiler trace (``.xplane.pb``, read with
``jax.profiler.ProfileData``) to the numbers the per-layer metrics read.

The window is the benchmark's own ``bench.window`` annotation on the
host; the check's fetches (``bench.fetch``) are cut out of it.  On each
device plane (``/device:TPU:<n>``) the op events (line ``XLA Ops``) give
the busy time, their union over the window, and the program events
(line ``XLA Modules``) the device time of named programs.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Tuple

import numpy as np

from harness import FETCH, WINDOW

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
PREFIX = "bench."  # every annotation of the benchmark's own


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of ``intervals``."""
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float,
         cuts: Iterable[Interval] = ()) -> List[Interval]:
    """The cover of ``intervals`` within [lo, hi], less ``cuts``: one
    merge over both sorted covers."""
    cuts = union(cuts)
    out = []
    j = 0
    for a, b in union(intervals):
        a, b = max(a, lo), min(b, hi)
        while j < len(cuts) and cuts[j][1] <= a:
            j += 1
        k = j
        while b > a and k < len(cuts) and cuts[k][0] < b:
            if cuts[k][0] > a:
                out.append((a, cuts[k][0]))
            a = max(a, cuts[k][1])
            k += 1
        if b > a:
            out.append((a, b))
    return out


def length(intervals: Iterable[Interval]) -> float:
    return float(sum(b - a for a, b in intervals))


@dataclasses.dataclass
class Device:
    ops: List[Tuple[str, float, float]]        # name, start, end (ns)
    modules: List[Tuple[str, float, float]]


@dataclasses.dataclass
class Reduced:
    """One traced window, times in seconds unless named ``_ns``."""

    window_ns: Interval
    cuts_ns: List[Interval]                  # the check's fetches
    annotations: List[Tuple[str, float, float]]
    devices: List[Device]

    @property
    def window_s(self) -> float:
        lo, hi = self.window_ns
        return length(clip([(lo, hi)], lo, hi, self.cuts_ns)) * 1e-9

    def _busy(self, dev: Device) -> List[Interval]:
        lo, hi = self.window_ns
        return clip(((a, b) for _, a, b in dev.ops), lo, hi, self.cuts_ns)

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        return float(np.mean([length(self._busy(d))
                              for d in self.devices])) * 1e-9

    def module_names(self) -> Dict[str, int]:
        """How many times each program ran in the window."""
        lo, hi = self.window_ns
        seen = collections.Counter()
        for d in self.devices:
            for name, a, b in d.modules:
                if b > lo and a < hi:
                    seen[_program(name)] += 1
        return dict(seen)

    def module_seconds(self, programs: Iterable[str]) -> float:
        """Device seconds of the named programs in the window, averaged
        over the devices; a program is named by its HLO module name."""
        wanted = set(programs)
        lo, hi = self.window_ns
        total = []
        for d in self.devices:
            spans = [(a, b) for name, a, b in d.modules
                     if _program(name) in wanted]
            total.append(length(clip(spans, lo, hi, self.cuts_ns)))
        return float(np.mean(total)) * 1e-9 if total else 0.0

    def breakdown(self, top: int = 10) -> dict:
        """The device ops with the most self time in the window (an op's
        time less that of the ops inside it, as a loop's body), each
        named ``<program>:<HLO op>`` by the program (line ``XLA
        Modules``) it ran in, and the longest idle gaps, each labelled
        by the benchmark's annotation around it: a call, a fetch for the
        check, or between calls."""
        lo, hi = self.window_ns
        per_op = collections.Counter()
        gaps = []
        for d in self.devices:
            ops = _in_programs(d.ops, d.modules)
            for name, s in _self_times(ops, lo, hi).items():
                per_op[name] += s * 1e-9 / len(self.devices)
            free = clip([self.window_ns], lo, hi, self.cuts_ns)
            for a, b in clip(free, lo, hi, self._busy(d)):
                gaps.append((b - a, (a + b) / 2))
        gaps.sort(reverse=True)
        return {"device_ops": [[n, s] for n, s in per_op.most_common(top)],
                "idle_gaps": [[self._label(t), g * 1e-9]
                              for g, t in gaps[:top]]}

    def _label(self, t: float) -> str:
        inside = [(b - a, name) for name, a, b in self.annotations
                  if a <= t <= b and name != WINDOW]
        if not inside:
            return "between calls"
        return min(inside)[1]


def _self_times(ops, lo: float, hi: float) -> Dict[str, float]:
    """Self time (ns) of each op name within [lo, hi]: an op's duration
    less the durations of the ops nested in it."""
    out: Dict[str, float] = collections.defaultdict(float)
    stack: List[Tuple[str, float]] = []    # (short name, end)
    for a, neg_b, name in sorted((max(a, lo), -min(b, hi), name)
                                 for name, a, b in ops if b > lo and a < hi):
        b = -neg_b
        while stack and stack[-1][1] <= a:
            stack.pop()
        short = name.split(" = ", 1)[0]
        out[short] += b - a
        if stack:
            out[stack[-1][0]] -= b - a
        stack.append((short, b))
    return out


def _in_programs(ops, modules):
    """``ops`` with each name prefixed by the program it ran in:
    ``<program>:<op>``, ``?:<op>`` where no program holds it."""
    modules = sorted(modules, key=lambda m: m[1])
    starts = [a for _, a, _ in modules]
    out = []
    for name, a, b in ops:
        k = bisect.bisect_right(starts, a) - 1
        program = (_program(modules[k][0])
                   if k >= 0 and modules[k][2] >= a else "?")
        out.append((f"{program}:{name}", a, b))
    return out


def _program(name: str) -> str:
    """``jit_chain(12)`` and ``jit_chain.3`` are both ``jit_chain``."""
    return re.split(r"[(.]", name, maxsplit=1)[0]


def reduce(path: str) -> Reduced:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    annotations = []
    devices = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events]
                elif line.name == MODULES_LINE:
                    modules = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events]
            devices.append(Device(ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        annotations.append(
                            (e.name, e.start_ns, e.start_ns + e.duration_ns))
    windows = [(a, b) for n, a, b in annotations if n == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW} annotation in {path}")
    window = max(windows, key=lambda w: w[1] - w[0])
    cuts = union((a, b) for n, a, b in annotations if n == FETCH)
    return Reduced(window, cuts, annotations, devices)
