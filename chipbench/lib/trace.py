"""From a profiler trace to the numbers the per-layer metrics read.

:func:`from_xplane` reads the ``.xplane.pb`` that ``jax.profiler`` writes
into a small plain form, and :func:`reduce` computes from that form alone,
so a recorded trace (``chipbench/data/``) checks the arithmetic without a
chip.  The plain form::

    {"window": [start_ns, end_ns],             # the benchmark's "window" span
     "devices": {plane: [[op, start_ns, dur_ns], ...]},   # "XLA Ops" lines
     "host": [[label, start_ns, dur_ns], ...]}  # the benchmark's own spans

Device busy time is the union of the op intervals inside the window, per
chip; idle is the rest of the window.  Each idle stretch is put down to
the innermost benchmark span on the host that covers its midpoint
(``feed``, ``backend.run:<name>``, ...), or to ``none``.
"""

from __future__ import annotations

import bisect
import re

__all__ = ["COLLECTIVE", "HOST_LABELS", "from_xplane", "reduce"]

# the spans the driver opens (driver.Spans); nothing else on the host counts
HOST_LABELS = ("window", "generate", "feed", "poll", "drain", "wait",
               "check")
HOST_PREFIXES = ("backend.run:",)
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute")


def _ours(name: str) -> bool:
    return name in HOST_LABELS or name.startswith(HOST_PREFIXES)


def from_xplane(path: str) -> dict:
    """The plain form of one trace file (see the module docstring)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        [e.name, e.start_ns, e.duration_ns]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in line.events if _ours(e.name))
    windows = [h for h in host if h[0] == "window"]
    if len(windows) != 1:
        raise ValueError(f"expected one 'window' span, found {len(windows)}")
    _, start, dur = windows[0]
    return {"window": [start, start + dur], "devices": devices,
            "host": [h for h in host if h[0] != "window"]}


def _union(intervals: list[tuple[float, float]]) -> list[list[float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


class _HostIndex:
    """Innermost host span covering an instant.  The spans come from one
    thread, so they nest; each keeps a pointer to its enclosing span."""

    def __init__(self, spans: list):
        self._spans = sorted((float(s), float(s) + float(d), name)
                             for name, s, d in spans)
        self._starts = [s for s, _, _ in self._spans]
        self._parent: list[int] = []
        stack: list[int] = []
        for i, (s, _, _) in enumerate(self._spans):
            while stack and self._spans[stack[-1]][1] <= s:
                stack.pop()
            self._parent.append(stack[-1] if stack else -1)
            stack.append(i)

    def label(self, t: float) -> str:
        i = bisect.bisect_right(self._starts, t) - 1
        while i >= 0 and not t < self._spans[i][1]:
            i = self._parent[i]
        return self._spans[i][2] if i >= 0 else "none"


def reduce(trace: dict) -> dict:
    """Busy, idle and per-op seconds of the traced window, per chip mean.

    Returns ``window_s``, ``busy_s``, ``chips``, ``op_s`` (op name ->
    seconds), ``collective_s`` and ``idle_by_host`` (label -> seconds).
    """
    w0, w1 = (float(v) for v in trace["window"])
    if not w1 > w0:
        raise ValueError("empty trace window")
    host = _HostIndex(trace["host"])
    chips = len(trace["devices"])
    op_s: dict[str, float] = {}
    idle: dict[str, float] = {}
    busy = coll = 0.0
    for events in trace["devices"].values():
        spans = []
        for name, s, d in events:
            s, e = max(float(s), w0), min(float(s) + float(d), w1)
            if e <= s:
                continue
            spans.append((s, e))
            # an op's event name is its HLO text; the instruction name
            # before " = " is what tells ops apart (all-reduce.3, ...)
            op = name.split(" = ", 1)[0]
            op_s[op] = op_s.get(op, 0.0) + (e - s)
            if COLLECTIVE.search(op):
                coll += e - s
        merged = _union(spans)
        busy += sum(e - s for s, e in merged)
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                lab = host.label((a + b) / 2.0)
                idle[lab] = idle.get(lab, 0.0) + (b - a)
    div = max(chips, 1) * 1e9
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy / div,
        "chips": chips,
        "op_s": {k: v / div for k, v in op_s.items()},
        "collective_s": coll / div,
        "idle_by_host": {k: v / div for k, v in idle.items()},
    }
