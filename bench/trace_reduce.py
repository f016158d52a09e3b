"""From a profiler trace to the device's busy time, step time and idle gaps.

A trace here is a list of planes, each ``{"name": ..., "lines": {line name:
[[event name, start ns, duration ns], ...]}}``: ``load_xplane`` makes it
from the ``.xplane.pb`` that ``jax.profiler`` writes, and the recorded trace
in ``bench/tests/data`` is kept in the same form. Host and device events share
one clock in it.

- Busy: the union of the intervals of the operations on each TPU's
  ``XLA Ops`` line, clipped to the window; averaged over the TPUs that ran any.
- Step device time: every program run on the ``XLA Modules`` line that
  starts inside a host span ``bench.step:<model>``, which the harness opens
  around each step (the step program and the small one that uploads its
  token), counted per span, so a step is one span whatever its programs are
  named.
- Idle gaps: the holes in the busy union, each put to what the host was doing
  at its middle: a step, ``serve`` outside a step, or waiting for arrivals.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
STEP_SPAN = "bench.step:"
HOST_ACTIVITY = (  # (span name prefix, label), most specific first
    (STEP_SPAN, "in step"),
    ("bench.serve", "in serve outside steps"),
    ("bench.wait", "waiting for arrivals"),
)


def load_xplane(path: str) -> List[dict]:
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            lines[line.name] = [[e.name, e.start_ns, e.duration_ns] for e in line.events]
        planes.append({"name": plane.name, "lines": lines})
    return planes


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted, disjoint cover of (start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, w0, w1):
    return [(max(s, w0), min(e, w1)) for s, e in intervals if e > w0 and s < w1]


def device_planes(trace: List[dict]) -> List[dict]:
    return [p for p in trace if DEVICE_PLANE.match(p["name"])]


def host_spans(trace: List[dict], prefix: str = "bench.") -> List[Tuple[str, float, float]]:
    """(name, start, end) of every host event whose name starts with prefix."""
    out = []
    for p in trace:
        if DEVICE_PLANE.match(p["name"]):
            continue
        for events in p["lines"].values():
            out.extend((n, s, s + d) for n, s, d in events if n.startswith(prefix))
    return sorted(out, key=lambda x: x[1])


def window(trace: List[dict]) -> Tuple[float, float]:
    spans = [(s, e) for n, s, e in host_spans(trace, WINDOW_SPAN) if n == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(spans)}")
    return spans[0]


def busy_intervals(plane: dict, w0: float, w1: float) -> List[Tuple[float, float]]:
    ops = plane["lines"].get(OPS_LINE, [])
    return union(_clip(((s, s + d) for _, s, d in ops), w0, w1))


def busy_ns(trace: List[dict], w0: float, w1: float) -> Optional[float]:
    """Busy time averaged over the TPUs that ran an operation in the window."""
    per_chip = [sum(e - s for s, e in busy_intervals(p, w0, w1)) for p in device_planes(trace)]
    per_chip = [b for b in per_chip if b > 0]
    return sum(per_chip) / len(per_chip) if per_chip else None


def step_runs(trace: List[dict], w0: float, w1: float) -> Dict[int, Tuple[int, float]]:
    """model index -> (steps, their summed device ns): the steps are the
    ``bench.step:<model>`` spans in the window in which some program ran, and
    a step's device time is that of every program run starting inside it."""
    steps = [(s, e, int(n[len(STEP_SPAN):])) for n, s, e in host_spans(trace, STEP_SPAN) if w0 <= s < w1]
    starts = [s for s, _, _ in steps]
    per_span: Dict[int, float] = defaultdict(float)
    for p in device_planes(trace):
        for _, s, d in p["lines"].get(MODULES_LINE, []):
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < steps[i][1]:
                per_span[i] += d
    out: Dict[int, List[float]] = defaultdict(lambda: [0, 0.0])
    for i, ns in per_span.items():
        acc = out[steps[i][2]]
        acc[0] += 1
        acc[1] += ns
    return {m: (n, ns) for m, (n, ns) in out.items()}


def top_ops(trace: List[dict], w0: float, w1: float, k: int = 10) -> List[Tuple[str, float]]:
    """The k operations (by the first 120 characters of their HLO) with the
    most device ns in the window. An op that encloses the next one on its
    line, as a ``while`` encloses its body, is left out, so time is not
    counted twice."""
    total: Dict[str, float] = defaultdict(float)
    for p in device_planes(trace):
        ops = sorted((s, s + d, n) for n, s, d in p["lines"].get(OPS_LINE, []) if w0 <= s < w1)
        for (s, e, n), nxt in zip(ops, ops[1:] + [None]):
            if nxt is not None and nxt[0] < e and nxt[1] <= e:
                continue
            total[n[:120]] += e - s
    return sorted(total.items(), key=lambda x: -x[1])[:k]


def idle_gaps(trace: List[dict], w0: float, w1: float) -> Dict[str, Tuple[int, float]]:
    """label -> (gaps, summed ns) of the holes in the first busy TPU's op
    union, each labelled by the host span at its middle."""
    planes = [p for p in device_planes(trace) if busy_intervals(p, w0, w1)]
    if not planes:
        return {}
    busy = busy_intervals(planes[0], w0, w1)
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    spans = host_spans(trace)
    covers = []  # per activity: the union of its spans and their starts
    for prefix, name in HOST_ACTIVITY:
        u = union((s, e) for n, s, e in spans if n.startswith(prefix))
        covers.append((name, u, [s for s, _ in u]))
    out: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    for gs, ge in zip(edges[::2], edges[1::2]):
        if ge <= gs:
            continue
        mid = (gs + ge) / 2
        label = "harness"
        for name, u, starts in covers:
            i = bisect.bisect_right(starts, mid) - 1
            if i >= 0 and mid < u[i][1]:
                label = name
                break
        out[label][0] += 1
        out[label][1] += ge - gs
    return {k: (int(n), ns) for k, (n, ns) in out.items()}
