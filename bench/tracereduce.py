"""Reduce a JAX profiler trace to device busy time, kernel and collective
times, and a short breakdown.

Everything below ``TraceView`` works on plain lists of
``(name, start_ns, end_ns)``, so the arithmetic is tested without a
trace. ``TraceView.load`` reads the ``.xplane.pb`` that
``jax.profiler.stop_trace`` writes, with JAX's own ``ProfileData``.

The window is the union of the host spans that the harness opens around
each measured episode (``WINDOW_SPAN``). A device is busy where at least
one of its operations runs; the idle share is one minus busy over the
window.

On a TPU the ``XLA Ops`` line of ``/device:TPU:<n>`` holds one event per
executed HLO op, named by its HLO text (``%fedagg_op.1 = f32[...]
custom-call(...)``). Control-flow ops (``%while``, ``%cond``) are
events too and span the ops of their bodies: the busy union is not
changed by them, and the breakdown lists leaf ops only.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Iterable, Optional

WINDOW_SPAN = "bench.episode"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"

Interval = tuple[int, int]
Event = tuple[str, int, int]


def merge(intervals: Iterable[Interval]) -> list[Interval]:
    """Union of half-open intervals, sorted and disjoint."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def intersect(a: list[Interval], b: list[Interval]) -> list[Interval]:
    """Intersection of two sorted disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: list[Interval], b: list[Interval]) -> list[Interval]:
    """``a`` minus ``b``, both sorted and disjoint."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def busy_ns(ops: list[Event], window: list[Interval]) -> int:
    """Time in the window during which at least one op runs."""
    return total(intersect(merge((s, e) for _, s, e in ops), window))


def matching_ns(ops: list[Event], pattern: str,
                window: list[Interval]) -> int:
    """Summed durations, inside the window, of the ops whose name
    matches ``pattern`` (a regular expression, searched)."""
    rx = re.compile(pattern)
    return sum(total(intersect([(s, e)], window))
               for n, s, e in ops if rx.search(n))


def leaves(ops: list[Event]) -> list[Event]:
    """The ops that contain no other op of the list."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    parent, stack = set(), []
    for i in order:
        _, s, e = ops[i]
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][2]:
            parent.add(stack[-1])
        stack.append(i)
    return [op for i, op in enumerate(ops) if i not in parent]


def short_name(hlo: str) -> str:
    """``%fusion.7 = bf16[55296,28,28]{...} fusion(...)`` ->
    ``%fusion.7 bf16[55296,28,28] fusion``."""
    m = re.match(r"(%[\w.-]+) = (\S+?)(?:\{[^ ]*\})? ([\w-]+)\(", hlo)
    return " ".join(m.groups()) if m else hlo[:80]


def exposed_ns(ops: list[Event], pattern: str,
               window: list[Interval]) -> int:
    """Time inside the window in which an op matching ``pattern`` runs
    and no other leaf op does (a ``%while`` around the collective does
    not hide it)."""
    rx = re.compile(pattern)
    coll = merge((s, e) for n, s, e in ops if rx.search(n))
    other = merge((s, e) for n, s, e in leaves(ops) if not rx.search(n))
    return total(intersect(subtract(coll, other), window))


def top_ops(devices: list[list[Event]], window: list[Interval],
            n: int = 10) -> list[list]:
    """The leaf ops that took the most device time in the window, summed
    by name and averaged over the devices: ``[[name, seconds], ...]``."""
    acc: dict[str, int] = defaultdict(int)
    for ops in devices:
        for name, s, e in leaves(ops):
            acc[short_name(name)] += total(intersect([(s, e)], window))
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9 / max(1, len(devices))] for name, ns in ranked
            if ns > 0]


def idle_gaps(ops: list[Event], window: list[Interval],
              host: list[Event], n: int = 10) -> list[list]:
    """The longest gaps in one device's work inside the window, each
    named by the innermost host event that covers its middle:
    ``[["<host event> @<offset s>", seconds], ...]``."""
    busy = merge((s, e) for _, s, e in ops)
    gaps = sorted(subtract(window, busy), key=lambda g: g[0] - g[1])[:n]
    t0 = window[0][0] if window else 0
    out = []
    for s, e in gaps:
        mid = (s + e) // 2
        cover = [(hs, he, name) for name, hs, he in host if hs <= mid < he]
        label = min(cover, key=lambda c: c[1] - c[0])[2] if cover \
            else "no host event"
        out.append([f"{label} @{(s - t0) / 1e9:.3f}s", (e - s) / 1e9])
    return out


@dataclasses.dataclass
class TraceView:
    """Device ops per chip, host events and the measured window."""
    devices: dict[int, list[Event]]
    host: list[Event]
    window: list[Interval]

    @classmethod
    def load(cls, trace_dir: str, span: str = WINDOW_SPAN) -> "TraceView":
        from jax.profiler import ProfileData
        paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(paths) != 1:
            raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                               f"found {len(paths)}")
        return cls.from_profile(ProfileData.from_file(paths[0]), span)

    @classmethod
    def from_profile(cls, pd, span: str = WINDOW_SPAN) -> "TraceView":
        devices: dict[int, list[Event]] = {}
        host: list[Event] = []
        for plane in pd.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                ops = []
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        ops += [(ev.name, int(ev.start_ns), int(ev.end_ns))
                                for ev in line.events]
                devices[int(m.group(1))] = ops
            elif plane.name == "/host:CPU":
                for line in plane.lines:
                    host += [(ev.name, int(ev.start_ns), int(ev.end_ns))
                             for ev in line.events]
        window = merge((s, e) for n, s, e in host if n == span)
        return cls(devices, host, window)

    # ------------------------------------------------------------------
    @property
    def window_s(self) -> float:
        return total(self.window) / 1e9

    def chips(self, n: Optional[int] = None) -> list[list[Event]]:
        ids = sorted(self.devices)[:n] if n else sorted(self.devices)
        return [self.devices[i] for i in ids]

    def busy_s(self, n: Optional[int] = None) -> float:
        """Busy seconds, averaged over the first ``n`` chips."""
        ch = self.chips(n)
        return sum(busy_ns(o, self.window) for o in ch) / 1e9 / len(ch)

    def op_s(self, pattern: str, n: Optional[int] = None) -> float:
        """Seconds of ops matching ``pattern``, averaged over chips."""
        ch = self.chips(n)
        return sum(matching_ns(o, pattern, self.window)
                   for o in ch) / 1e9 / len(ch)

    def exposed_s(self, pattern: str, n: Optional[int] = None) -> float:
        """Seconds in which an op matching ``pattern`` runs alone,
        averaged over chips."""
        ch = self.chips(n)
        return sum(exposed_ns(o, pattern, self.window)
                   for o in ch) / 1e9 / len(ch)

    def breakdown(self, n: Optional[int] = None) -> dict:
        return {"device_ops": top_ops(self.chips(n), self.window),
                "idle_gaps": idle_gaps(self.chips(n)[0], self.window,
                                       self.host)}
