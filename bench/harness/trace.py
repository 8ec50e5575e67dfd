"""Reduction of a JAX profiler trace to the benchmark's device numbers.

A trace is reduced to three kinds of intervals, all on one clock, in
nanoseconds:

- ``host``: the spans of the thread that ran the measured window, the
  benchmark's own ``TraceAnnotation``s (``window``, ``merge``, ...) and
  the events JAX records there (``PjitFunction(decode_step)``, ...);
- ``ops``: per chip, every operation the device ran (line ``XLA Ops``);
- ``modules``: per chip, every program the device ran (``XLA Modules``),
  named ``jit_<function>(<id>)``.

The window is the benchmark's ``window`` span. Busy time is the union of
a chip's operation intervals inside the window; numbers over several
chips are their mean.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")

Interval = Tuple[float, float]


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    host: List[Event]
    ops: List[List[Event]]
    modules: List[List[Event]]

    @property
    def window(self) -> Interval:
        spans = [e for e in self.host if e.name == WINDOW_SPAN]
        if len(spans) != 1:
            raise ValueError(f"expected one {WINDOW_SPAN!r} span in the "
                             f"trace, found {len(spans)}")
        return spans[0].start, spans[0].end


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(glob.escape(log_dir), "**",
                                   "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {log_dir}, "
                         f"found {len(paths)}")
    return paths[0]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` with JAX's own reader."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host_lines: List[List[Event]] = []
    ops: List[List[Event]] = []
    modules: List[List[Event]] = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host_lines.append([Event(e.name, e.start_ns, e.end_ns)
                                   for e in line.events])
        elif DEVICE_PLANE.match(plane.name):
            by_line = {line.name: [Event(e.name, e.start_ns, e.end_ns)
                                   for e in line.events]
                       for line in plane.lines}
            if OPS_LINE in by_line:
                ops.append(by_line[OPS_LINE])
                modules.append(by_line.get(MODULES_LINE, []))
    return from_lines(host_lines, ops, modules)


def from_lines(host_lines: Sequence[Sequence[Event]],
               ops: Sequence[Sequence[Event]],
               modules: Sequence[Sequence[Event]]) -> Trace:
    """Keep the host line that holds the ``window`` span."""
    host = [line for line in host_lines
            if any(e.name == WINDOW_SPAN for e in line)]
    if len(host) != 1:
        raise ValueError(f"expected one host thread with a {WINDOW_SPAN!r} "
                         f"span, found {len(host)}")
    return Trace(sorted(host[0], key=lambda e: (e.start, -e.end)),
                 [sorted(o, key=lambda e: e.start) for o in ops],
                 [sorted(m, key=lambda e: e.start) for m in modules])


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def intersect(a: Sequence[Interval], b: Sequence[Interval]
              ) -> List[Interval]:
    """Intersection of two unions (sorted, disjoint)."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: Sequence[Interval], b: Sequence[Interval]
             ) -> List[Interval]:
    """``a`` minus ``b``, both unions."""
    out = []
    for s, e in a:
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
        if cur < e:
            out.append((cur, e))
    return out


# ---------------------------------------------------------------------------
# the numbers
# ---------------------------------------------------------------------------

NS = 1e-9


def window_s(tr: Trace) -> float:
    lo, hi = tr.window
    return (hi - lo) * NS


def busy(tr: Trace) -> List[List[Interval]]:
    """Per chip, the union of its operation intervals inside the window."""
    lo, hi = tr.window
    return [clip(union((e.start, e.end) for e in ops), lo, hi)
            for ops in tr.ops]


def _mean(xs: Sequence[float]) -> Optional[float]:
    return sum(xs) / len(xs) if xs else None


def busy_s(tr: Trace) -> Optional[float]:
    return _mean([total(b) * NS for b in busy(tr)])


def idle_share(tr: Trace) -> Optional[float]:
    b = busy_s(tr)
    return None if b is None else 1.0 - b / window_s(tr)


def spans(tr: Trace, name: str) -> List[Interval]:
    """Union of the host spans called ``name``, inside the window."""
    lo, hi = tr.window
    return clip(union((e.start, e.end) for e in tr.host if e.name == name),
                lo, hi)


def device_s_in(tr: Trace, span_name: str) -> Optional[float]:
    """Busy device time inside the host spans ``span_name``."""
    s = spans(tr, span_name)
    return _mean([total(intersect(b, s)) * NS for b in busy(tr)])


def idle_s_in(tr: Trace, span_name: str) -> Optional[float]:
    """Time inside the host spans ``span_name`` in which the device ran
    nothing."""
    s = spans(tr, span_name)
    return _mean([total(subtract(s, b)) * NS for b in busy(tr)])


def module_name(raw: str) -> str:
    """``jit_decode_step(123)`` -> ``decode_step``."""
    name = re.sub(r"\(\d+\)$", "", raw)
    return name[4:] if name.startswith("jit_") else name


def module_s(tr: Trace, name: str) -> Tuple[float, int]:
    """Device seconds and event count of the programs ``name`` (as
    :func:`module_name` gives it) inside the window, mean over chips."""
    lo, hi = tr.window
    secs, counts = [], []
    for mods in tr.modules:
        hits = [(e.start, e.end) for e in mods if module_name(e.name) == name]
        secs.append(total(clip(union(hits), lo, hi)) * NS)
        counts.append(len(clip(hits, lo, hi)))
    return (_mean(secs) or 0.0), (max(counts) if counts else 0)


def _enclosing(events: Sequence[Event], starts: Sequence[float],
               t: float) -> Optional[Event]:
    """The shortest event that contains ``t``."""
    best = None
    for e in events[:bisect.bisect_right(starts, t)]:
        if e.end > t and (best is None or e.dur < best.dur):
            best = e
    return best


def idle_gaps(tr: Trace) -> List[Interval]:
    """Chip 0's idle intervals inside the window."""
    lo, hi = tr.window
    if not tr.ops:
        return []
    return subtract([(lo, hi)], busy(tr)[0])


def op_name(raw: str) -> str:
    """An operation event is named by its HLO instruction
    (``%fusion.195 = bf16[64,6400] fusion(...)``): keep ``fusion.195``."""
    return raw.split(" = ", 1)[0].lstrip("%")


def leaf_ops(ops: Sequence[Event]) -> List[Event]:
    """The operations that contain no other: a loop (``while``) is
    recorded around the operations of its body, which are kept instead."""
    out = []
    for i, e in enumerate(ops):
        if i + 1 < len(ops) and ops[i + 1].start < e.end and \
                ops[i + 1].end <= e.end:
            continue
        out.append(e)
    return out


def breakdown(tr: Trace, top: int = 10) -> Dict[str, list]:
    """The device operations that took most time (``program:op``, chip
    0, inside the window, loops counted by their bodies) and the longest
    idle gaps of chip 0, each named by the innermost host span around its
    middle."""
    lo, hi = tr.window
    per: Dict[str, float] = {}
    if tr.ops:
        mods = tr.modules[0]
        mstart = [m.start for m in mods]
        for e in leaf_ops(tr.ops[0]):
            s, t = max(e.start, lo), min(e.end, hi)
            if t <= s:
                continue
            # programs run one after another on a chip: the last one to
            # start before the op's middle is the only candidate
            mid = (e.start + e.end) / 2
            i = bisect.bisect_right(mstart, mid) - 1
            m = mods[i] if i >= 0 and mods[i].end > mid else None
            key = f"{module_name(m.name) if m else '?'}:{op_name(e.name)}"
            per[key] = per.get(key, 0.0) + (t - s) * NS
    ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
    hstart = [e.start for e in tr.host]
    gaps = []
    for s, e in sorted(idle_gaps(tr), key=lambda g: g[0] - g[1])[:top]:
        span = _enclosing(tr.host, hstart, (s + e) / 2)
        gaps.append([span.name if span else "no host span", (e - s) * NS])
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": gaps}
