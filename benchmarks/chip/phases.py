"""The program's own spans in a kept profiler trace: where the device sat
idle, by the phase of the program that held the host.

While ``repro.obs.trace``'s collector is on, each open span holds a
``jax.profiler.TraceAnnotation`` of its name, so a traced run made with
``REPRO_TRACE=1`` records the program's spans (``query.group_by``,
``query.gather``, ...) on the host plane, on the device ops' clock::

    REPRO_TRACE=1 python3 benchmarks/chip/run.py --workload tpch_sf10.q1 \\
        --seed 7 --seconds 20 --trace 1 --keep-trace DIR
    python3 benchmarks/chip/phases.py DIR/*.xplane.pb

prints one JSON object over the window ``tracefile`` reduces (the
check's fetches cut out): the idle seconds cut at the spans' boundaries
and summed by the innermost span around each piece (else the
benchmark's annotation, as ``tracefile`` labels gaps), the longest such
pieces, each span name's count and self seconds, and per call the idle
milliseconds inside the operator's span (``--operator``).  The union of
the pieces is the window's idle time, to the nanosecond.
"""

from __future__ import annotations

import argparse
import bisect
import collections
import dataclasses
import json
import re
import sys
from pathlib import Path
from typing import Dict, List, Optional, Tuple

HERE = Path(__file__).resolve().parent
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import numpy as np  # noqa: E402

import tracefile  # noqa: E402
from harness import CALL, WINDOW  # noqa: E402

# a program span's name: two or more dotted lowercase words, each
# starting with a letter, and not the benchmark's own annotation; never
# one of JAX's own host events (``PjitFunction(f)``, ``$api.py:3097
# ...``, ``np.asarray(jax.Array)``) nor an XLA op run on the host
# (``copy.15``, ``while.1``)
PROGRAM_SPAN = re.compile(rf"^(?!{re.escape(tracefile.PREFIX)})"
                          r"[a-z][a-z0-9_]*(\.[a-z][a-z0-9_]*)+$")
OPERATOR = "query.group_by"

Span = Tuple[str, float, float]


def program_spans(path: str) -> List[Span]:
    """The program's spans on the host planes of the trace at ``path``,
    as ``(name, start_ns, end_ns)``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events if PROGRAM_SPAN.match(e.name)]
    return out


@dataclasses.dataclass
class Phases:
    """A reduced window and the program's spans on the same clock."""

    reduced: tracefile.Reduced
    spans: List[Span]

    def _busy(self, d: tracefile.Device):
        lo, hi = self.reduced.window_ns
        return tracefile.clip(((a, b) for _, a, b in d.ops), lo, hi,
                              self.reduced.cuts_ns)

    def idle(self, d: tracefile.Device) -> List[tracefile.Interval]:
        """The window's idle gaps on ``d``, each cut at the boundaries
        of the program's spans, so that one span, or none, holds each
        piece innermost."""
        lo, hi = self.reduced.window_ns
        free = tracefile.clip([(lo, hi)], lo, hi, self.reduced.cuts_ns)
        edges = sorted({t for _, a, b in self.spans for t in (a, b)})
        pieces = []
        for a, b in tracefile.clip(free, lo, hi, self._busy(d)):
            k = bisect.bisect_right(edges, a)
            while k < len(edges) and edges[k] < b:
                pieces.append((a, edges[k]))
                a = edges[k]
                k += 1
            pieces.append((a, b))
        return pieces

    def label(self, t: float) -> str:
        """The shortest program span or benchmark annotation around
        ``t``: a phase of the program, a call, a fetch for the check, or
        between calls."""
        inside = [(b - a, name)
                  for name, a, b in self.reduced.annotations + self.spans
                  if a <= t <= b and name != WINDOW]
        return min(inside)[1] if inside else "between calls"

    def idle_by_label(self) -> Dict[str, float]:
        """Idle seconds of the window by the label of each piece,
        averaged over the devices."""
        out: Dict[str, float] = collections.defaultdict(float)
        n = len(self.reduced.devices)
        for d in self.reduced.devices:
            for a, b in self.idle(d):
                out[self.label((a + b) / 2)] += (b - a) * 1e-9 / n
        return dict(out)

    def longest_idle(self, top: int = 10) -> List[list]:
        """The ``top`` longest idle pieces, ``[label, seconds]``."""
        pieces = sorted(((b - a, (a + b) / 2)
                         for d in self.reduced.devices
                         for a, b in self.idle(d)), reverse=True)
        return [[self.label(t), g * 1e-9] for g, t in pieces[:top]]

    def idle_within(self, name: str) -> Optional[float]:
        """Seconds inside the spans named ``name``, within the window
        less the check's fetches, in which no op ran on the device,
        averaged over the devices; None where the trace has no device
        or no such span."""
        inside = [(a, b) for n, a, b in self.spans if n == name]
        if not self.reduced.devices or not inside:
            return None
        lo, hi = self.reduced.window_ns
        held = tracefile.clip(inside, lo, hi, self.reduced.cuts_ns)
        return float(np.mean([
            tracefile.length(tracefile.clip(held, lo, hi, self._busy(d)))
            for d in self.reduced.devices])) * 1e-9

    def calls(self) -> int:
        """The benchmark's calls in the window."""
        return sum(n.startswith(CALL) for n, _, _ in self.reduced.annotations)

    def self_seconds(self) -> Dict[str, list]:
        """``{name: [count, self seconds]}`` of the spans in the window,
        a span's self time being its time less that of the spans inside
        it."""
        lo, hi = self.reduced.window_ns
        inside = [s for s in self.spans if s[2] > lo and s[1] < hi]
        count = collections.Counter(n for n, _, _ in inside)
        self_ns = tracefile._self_times(inside, lo, hi)
        return {n: [count[n], self_ns[n] * 1e-9] for n in sorted(count)}

    def report(self, operator: str = OPERATOR, top: int = 10) -> dict:
        held = self.idle_within(operator)
        calls = self.calls()
        return {"calls": calls, "window_s": self.reduced.window_s,
                "busy_s": self.reduced.busy_s,
                "idle_s_by_label": self.idle_by_label(),
                "longest_idle": self.longest_idle(top),
                "span_count_and_self_s": self.self_seconds(),
                f"{operator}.idle_ms_per_call":
                    None if held is None or not calls
                    else 1e3 * held / calls}


def read(path: str) -> Phases:
    return Phases(tracefile.reduce(path), program_spans(path))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("xplane", help="a kept .xplane.pb")
    ap.add_argument("--operator", default=OPERATOR,
                    help="the span whose idle time a call is reported")
    ap.add_argument("--top", type=int, default=10)
    args = ap.parse_args(argv)
    print(json.dumps(read(args.xplane).report(args.operator, args.top)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
