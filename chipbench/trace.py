"""Reduction of a ``jax.profiler`` trace to what the per-layer metrics
read.

:func:`reduce_xspace` keeps, from an ``.xplane.pb``, each traced TPU's
ops and module launches and the host spans that the benchmark writes
around every request.  The reduced form is plain JSON, so a small one is
kept as a test fixture.  All times are nanoseconds on the trace's clock;
device and host events share it.

:class:`Trace` answers the questions the metrics ask over the traced
window: from the start of the first request that the trace holds whole to
the end of the last one that every device's record outlasts.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
LINES = {"XLA Ops": "ops", "XLA Modules": "modules"}
#: the benchmark's own spans, outermost first
HOST_SPANS = ("request", "put_input", "session_run", "fetch_output")
#: what the host did in a device gap that no request span covers
BETWEEN = "between_requests"

Interval = Tuple[int, int]


def reduce_xspace(path: str, devices: int) -> Dict:
    """The first ``devices`` TPUs' ``ops`` and ``modules`` as
    ``[name, start_ns, dur_ns]`` rows, and the host spans by name as
    ``[start_ns, dur_ns]`` rows."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devs: Dict[int, Dict] = {}
    host: Dict[str, List] = {n: [] for n in HOST_SPANS}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            d = devs.setdefault(int(m.group(1)),
                                {k: [] for k in LINES.values()})
            for line in plane.lines:
                key = LINES.get(line.name)
                if key is not None:
                    d[key].extend([e.name, int(e.start_ns),
                                   int(e.duration_ns)]
                                  for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in host:
                        host[e.name].append([int(e.start_ns),
                                             int(e.duration_ns)])
    if len(devs) < devices:
        raise ValueError(f"trace holds {len(devs)} TPU planes, the cell "
                         f"uses {devices}")
    for spans in host.values():
        spans.sort()
    return {"devices": [devs[i] for i in sorted(devs)[:devices]],
            "host": host}


def op_kind(name: str) -> str:
    """A TPU op's short name: its HLO instruction name without the ``%``
    and the copy number, and a custom call's target after a colon.  On a
    TPU the trace names an op by its whole HLO text."""
    short = re.sub(r"\.\d+$", "", name.split(" = ", 1)[0].lstrip("%"))
    target = re.search(r'custom_call_target="([^"]+)"', name)
    return f"{short}:{target.group(1)}" if target else short


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of the intervals."""
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _inside(spans: Sequence[Tuple[int, int]], t: int) -> bool:
    """Whether ``t`` lies in one of the sorted, disjoint
    ``(start, duration)`` spans."""
    i = bisect.bisect_right(spans, (t, float("inf"))) - 1
    return i >= 0 and t <= spans[i][0] + spans[i][1]


class Trace:
    """The traced window of a reduced trace."""

    def __init__(self, reduced: Dict):
        self.devices = reduced["devices"]
        self.host = {k: [tuple(s) for s in v]
                     for k, v in reduced["host"].items()}
        # The profiler can drop the end of one device's record (seen on
        # device 0 of four TPU v5e chips: its events stopped 0.3 s before
        # the other three's).  The window keeps the requests that every
        # device's record outlasts.
        cover = min((max((a + d for _, a, d in dv["ops"] + dv["modules"]),
                         default=-1) for dv in self.devices), default=-1)
        self.requests = [(a, a + d) for a, d in self.host["request"]
                         if a + d <= cover]
        if self.requests:
            self.lo = self.requests[0][0]
            self.hi = self.requests[-1][1]
        else:
            self.lo = self.hi = 0

    @property
    def images(self) -> int:
        return len(self.requests)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def ops(self, d: int, pattern: Optional[re.Pattern] = None
            ) -> List[Tuple[str, int, int]]:
        """Device ``d``'s ops that lie in the window (clipped to it),
        those whose name matches ``pattern`` where it is given."""
        out = []
        for name, a, dur in self.devices[d]["ops"]:
            if pattern is not None and not pattern.search(name):
                continue
            a2, b2 = max(a, self.lo), min(a + dur, self.hi)
            if b2 > a2:
                out.append((name, a2, b2))
        return out

    def busy(self, d: int) -> List[Interval]:
        return union((a, b) for _, a, b in self.ops(d))

    def busy_s(self, d: int) -> float:
        return sum(b - a for a, b in self.busy(d)) * 1e-9

    def op_seconds(self, pattern: re.Pattern) -> float:
        """Summed device time, over all traced devices, of the ops whose
        name matches ``pattern``; overlapping events count once."""
        return sum(sum(b - a for a, b in union(
            (a, b) for _, a, b in self.ops(d, pattern)))
            for d in range(len(self.devices))) * 1e-9

    def modules_in_requests(self, d: int) -> int:
        """Module launches on device ``d`` that start inside a request."""
        spans = [(a, b - a) for a, b in self.requests]
        return sum(_inside(spans, a) for _, a, _ in
                   self.devices[d]["modules"])

    def gaps(self, d: int) -> List[Interval]:
        """Device ``d``'s idle intervals inside the window."""
        out, t = [], self.lo
        for a, b in self.busy(d):
            if a > t:
                out.append((t, a))
            t = max(t, b)
        if self.hi > t:
            out.append((t, self.hi))
        return out

    def host_span_at(self, t: int) -> str:
        """The innermost benchmark span the host was in at ``t``."""
        found = BETWEEN
        for name in HOST_SPANS:
            if _inside(self.host[name], t):
                found = name
        return found

    def idle_by_span(self) -> List[List]:
        """Idle device seconds, averaged over devices, by the span the
        host was in at the middle of each gap, largest first."""
        tot: Dict[str, float] = {}
        for d in range(len(self.devices)):
            for a, b in self.gaps(d):
                k = self.host_span_at((a + b) // 2)
                tot[k] = tot.get(k, 0.0) + (b - a) * 1e-9
        n = len(self.devices)
        return sorted(([k, v / n] for k, v in tot.items()),
                      key=lambda kv: -kv[1])

    def top_ops(self, k: int = 10) -> List[List]:
        """Device seconds by op (:func:`op_kind`), averaged over devices,
        largest first."""
        tot: Dict[str, float] = {}
        for d in range(len(self.devices)):
            for name, a, b in self.ops(d):
                key = op_kind(name)
                tot[key] = tot.get(key, 0.0) + (b - a) * 1e-9
        n = len(self.devices)
        return sorted(([k2, v / n] for k2, v in tot.items()),
                      key=lambda kv: -kv[1])[:k]
