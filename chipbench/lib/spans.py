"""The program's own spans in a profiler trace: a tile's time, inside out.

``trace.py`` keeps the benchmark's spans and the device's ops.  This
module reads what the program puts on the same clock: its host spans,
``sortserve.*`` (``repro.obs.tracer.span``: feed, bucket, schedule,
execute, the backend call in it and that call's put / launch / wait /
fetch, compile, scatter), each with
its tile id, and the device plane's module events, one per executor call,
named after the executor (``jit_colskip``, ``jit_radix_topk``, ...).  The
plain form adds two keys to ``trace.from_xplane``'s::

    {"spans": [[name, start_ns, dur_ns, thread], ...],   # sortserve.*
     "modules": {plane: [[module, start_ns, dur_ns], ...]}}

:func:`reduce` computes from that form alone, like ``trace.reduce``.  A
program without these spans or a trace without the module line gives
empty tables, never an error.
"""

from __future__ import annotations

import bisect
import re

from .trace import DEVICE_PLANE, _union, from_xplane

__all__ = ["MODULES_LINE", "PREFIX", "from_xplane_with_spans", "reduce"]

PREFIX = "sortserve."
MODULES_LINE = "XLA Modules"
EXECUTOR = re.compile(r"^jit_([A-Za-z_]\w*?)(?:\(\d+\))?$")


def from_xplane_with_spans(path: str) -> dict:
    """``trace.from_xplane``'s plain form plus the program's spans and the
    device's module events."""
    from jax.profiler import ProfileData

    plain = from_xplane(path)
    spans: list = []
    modules: dict[str, list] = {}
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules[plane.name] = [
                        [e.name, e.start_ns, e.duration_ns]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                spans.extend([e.name, e.start_ns, e.duration_ns,
                              f"{plane.name}#{i}"]
                             for e in line.events
                             if e.name.startswith(PREFIX))
    return {**plain, "spans": spans, "modules": modules}


def _self_times(spans: list, w0: float, w1: float):
    """Per span name: seconds of self time inside the window (a span's
    duration minus its nested spans', per thread) and spans started in
    it."""
    self_s: dict[str, float] = {}
    count: dict[str, int] = {}
    by_thread: dict = {}
    for name, s, d, thread in spans:
        by_thread.setdefault(thread, []).append(
            (float(s), float(s) + float(d), name))
    for items in by_thread.values():
        # parents before children: earlier start first, longer first
        items.sort(key=lambda t: (t[0], -t[1]))
        stack: list[list] = []           # [start, end, name, child time]

        def close(top):
            s, e, name, child = top
            inside = max(0.0, min(e, w1) - max(s, w0))
            self_s[name] = self_s.get(name, 0.0) + max(0.0, inside - child)
            if w0 <= s < w1:
                count[name] = count.get(name, 0) + 1
            if stack:
                stack[-1][3] += inside

        for s, e, name in items:
            while stack and stack[-1][1] <= s:
                close(stack.pop())
            stack.append([s, e, name, 0.0])
        while stack:
            close(stack.pop())
    return ({k: v / 1e9 for k, v in self_s.items()}, count)


class _Innermost:
    """Innermost host span covering an instant, for spans that nest (one
    thread); of two spans that start together the longer is the outer."""

    def __init__(self, spans: list):
        self._spans = sorted(((float(s), float(s) + float(d), name)
                              for name, s, d in spans),
                             key=lambda t: (t[0], -t[1]))
        self._starts = [s for s, _, _ in self._spans]
        self._parent: list[int] = []
        stack: list[int] = []
        for s, _, _ in self._spans:
            while stack and self._spans[stack[-1]][1] <= s:
                stack.pop()
            self._parent.append(stack[-1] if stack else -1)
            stack.append(len(self._parent) - 1)

    def label(self, t: float) -> str:
        i = bisect.bisect_right(self._starts, t) - 1
        while i >= 0 and not t < self._spans[i][1]:
            i = self._parent[i]
        return self._spans[i][2] if i >= 0 else "none"


def reduce(trace: dict) -> dict:
    """What the program's spans and modules say about the traced window.

    ``span_self_s`` (name -> seconds) and ``span_count`` (name -> spans
    started in the window); ``executor_s`` and ``executor_calls`` (executor
    -> device seconds and module events, mean over chips for the seconds);
    ``idle_by_span``: the device's idle time put down to the innermost
    host span covering it, the program's spans and the benchmark's alike.
    """
    w0, w1 = (float(v) for v in trace["window"])
    spans = trace.get("spans", [])
    self_s, count = _self_times(spans, w0, w1)
    chips = max(len(trace["devices"]), 1)
    ex_s: dict[str, float] = {}
    ex_n: dict[str, int] = {}
    for events in trace.get("modules", {}).values():
        for name, s, d in events:
            m = EXECUTOR.match(name)
            s, e = max(float(s), w0), min(float(s) + float(d), w1)
            if m is None or e <= s:
                continue
            ex_s[m.group(1)] = ex_s.get(m.group(1), 0.0) + (e - s) / chips
            ex_n[m.group(1)] = ex_n.get(m.group(1), 0) + 1
    host = _Innermost(list(trace["host"])
                      + [[n, s, d] for n, s, d, _ in spans])
    idle: dict[str, float] = {}
    for events in trace["devices"].values():
        merged = _union([(max(float(s), w0), min(float(s) + float(d), w1))
                         for _, s, d in events
                         if min(float(s) + float(d), w1) > max(float(s), w0)])
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                lab = host.label((a + b) / 2.0)
                idle[lab] = idle.get(lab, 0.0) + (b - a)
    return {"span_self_s": self_s, "span_count": count,
            "executor_s": {k: v / 1e9 for k, v in ex_s.items()},
            "executor_calls": ex_n,
            "idle_by_span": {k: v / 1e9 / chips for k, v in idle.items()}}
