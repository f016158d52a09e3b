"""The live path's own spans in a trace, and the numbers they give.

The program writes ``msched.*`` host spans around each phase of the live
path, with its counts as the spans' stats (``docs/observability.md``, "Live
path spans"): ``msched.slice`` > ``msched.switch`` > ``msched.plan``,
``msched.evict``, ``msched.fetch``; ``msched.fault_service`` > the same
copies; ``msched.step`` > ``msched.step.dispatch``, ``msched.step.logits``.
``trace_reduce.load_xplane`` keeps three fields per event, so ``load`` reads
these spans apart, with their stats and thread. They share the device
planes' clock.

- ``self_time``: each span name's time not covered by a span nested in it.
- ``idle_by_span``: the holes in the first busy TPU's op union, each put to
  the innermost ``msched.*`` span at its middle, else to the harness's host
  activity as ``trace_reduce.idle_gaps`` labels it.
- ``copies``: the seconds and bytes of the evictions and fetches, by the
  switch or demand fault they served.
- ``evict_share``, ``h2d_gbps``, ``plan_ms``, ``step_idle_share``: the live
  path's per-layer numbers (``summarize``).

A trace without such spans, as an older program leaves, gives empty results
and None for each number.
"""
from __future__ import annotations

import bisect
import statistics
from collections import defaultdict
from typing import Dict, List, NamedTuple, Optional, Tuple

from bench import trace_reduce

PREFIX = "msched."
SWITCH, PLAN, EVICT, FETCH = "msched.switch", "msched.plan", "msched.evict", "msched.fetch"
FAULT, STEP = "msched.fault_service", "msched.step"


class Span(NamedTuple):
    name: str
    start: float  # ns
    end: float  # ns
    stats: dict
    line: str = ""  # the host thread's line: spans nest within one line


def load(path: str, prefix: str = PREFIX) -> List[Span]:
    """Every host event of an ``.xplane.pb`` whose name starts with prefix,
    sorted by start, outer spans before the spans they enclose."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    out.append(Span(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats), line.name))
    return sorted(out, key=lambda s: (s.start, -s.end))


def in_window(spans: List[Span], w0: float, w1: float) -> List[Span]:
    return [s for s in spans if w0 <= s.start < w1]


def _children(spans: List[Span]) -> Dict[int, List[int]]:
    """index -> indices of the spans directly inside it on its line."""
    kids: Dict[int, List[int]] = defaultdict(list)
    stacks: Dict[str, List[int]] = defaultdict(list)
    for i in sorted(range(len(spans)), key=lambda i: (spans[i].start, -spans[i].end)):
        s, stack = spans[i], stacks[spans[i].line]
        while stack and spans[stack[-1]].end <= s.start:
            stack.pop()
        if stack and s.end <= spans[stack[-1]].end:
            kids[stack[-1]].append(i)
        stack.append(i)
    return kids


def self_time(spans: List[Span]) -> Dict[str, float]:
    """name -> summed ns of its spans less the time of the spans directly
    inside them (on one thread, children do not overlap each other)."""
    kids = _children(spans)
    out: Dict[str, float] = defaultdict(float)
    for i, s in enumerate(spans):
        out[s.name] += (s.end - s.start) - sum(spans[k].end - spans[k].start for k in kids.get(i, ()))
    return dict(out)


def _first_busy(trace: List[dict], w0: float, w1: float) -> Optional[List[Tuple[float, float]]]:
    for p in trace_reduce.device_planes(trace):
        busy = trace_reduce.busy_intervals(p, w0, w1)
        if busy:
            return busy
    return None


def _innermost(spans: List[Span], points: List[float]) -> List[Optional[Span]]:
    """For each of the sorted points, the latest-starting span that holds it."""
    order = sorted(spans, key=lambda s: (s.start, -s.end))
    out, stack, j = [], [], 0
    for x in points:
        while j < len(order) and order[j].start <= x:
            stack.append(order[j])
            j += 1
        live = [s for s in stack if s.end > x]
        stack = live
        out.append(live[-1] if live else None)
    return out


def _activity(trace: List[dict]):
    """A function from a host time to the harness's activity there, the
    labels of ``trace_reduce.idle_gaps``."""
    host = trace_reduce.host_spans(trace)
    covers = []
    for prefix, name in trace_reduce.HOST_ACTIVITY:
        u = trace_reduce.union((s, e) for n, s, e in host if n.startswith(prefix))
        covers.append((name, u, [s for s, _ in u]))

    def label(x: float) -> str:
        for name, u, starts in covers:
            i = bisect.bisect_right(starts, x) - 1
            if i >= 0 and x < u[i][1]:
                return name
        return "harness"

    return label


def idle_by_span(trace: List[dict], spans: List[Span], w0: float, w1: float) -> Dict[str, Tuple[int, float]]:
    """label -> (gaps, summed ns): the holes of ``trace_reduce.idle_gaps``,
    labelled by the innermost program span at each hole's middle, or where
    there is none by the harness's activity there."""
    busy = _first_busy(trace, w0, w1)
    if busy is None:
        return {}
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    gaps = [(gs, ge) for gs, ge in zip(edges[::2], edges[1::2]) if ge > gs]
    mids = [(gs + ge) / 2 for gs, ge in gaps]
    activity = _activity(trace)
    out: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    for (gs, ge), mid, span in zip(gaps, mids, _innermost(spans, mids)):
        label = span.name if span is not None else activity(mid)
        out[label][0] += 1
        out[label][1] += ge - gs
    return {k: (int(n), ns) for k, (n, ns) in out.items()}


def _named(spans: List[Span], name: str) -> List[Span]:
    return [s for s in spans if s.name == name]


def _inside(inner: Span, outer: Span) -> bool:
    return outer.start <= inner.start and inner.end <= outer.end


def _owners(spans: List[Span]) -> Tuple[List[Span], List[float]]:
    """The spans that move a tenant in, switches and demand faults, which do
    not overlap, and their starts."""
    owners = sorted(_named(spans, SWITCH) + _named(spans, FAULT), key=lambda s: s.start)
    return owners, [s.start for s in owners]


def copies(spans: List[Span]) -> Dict[str, Dict[str, List[float]]]:
    """owner (``msched.switch`` or ``msched.fault_service``) -> copy
    (``msched.evict`` or ``msched.fetch``) -> [seconds, bytes]: where the
    bytes move and how long it takes."""
    owners, starts = _owners(spans)
    out: Dict[str, Dict[str, List[float]]] = defaultdict(lambda: defaultdict(lambda: [0.0, 0]))
    for c in _named(spans, EVICT) + _named(spans, FETCH):
        i = bisect.bisect_right(starts, c.start) - 1
        owner = owners[i].name if i >= 0 and _inside(c, owners[i]) else "none"
        acc = out[owner][c.name]
        acc[0] += (c.end - c.start) / 1e9
        acc[1] += c.stats.get("nbytes", 0)
    return {k: dict(v) for k, v in out.items()}


def evict_share(spans: List[Span]) -> Optional[float]:
    """Share (%) of the time spent moving tenants in (``msched.switch`` and
    ``msched.fault_service`` spans) that goes to ``msched.evict``, the
    device-to-host copies of what leaves."""
    owners, _ = _owners(spans)
    total = sum(s.end - s.start for s in owners)
    if not total:
        return None
    return 100.0 * sum(e.end - e.start for e in _named(spans, EVICT)) / total


def h2d_gbps(spans: List[Span]) -> Optional[float]:
    """Bytes the ``msched.fetch`` spans moved over their time (GB/s): from
    the first copy's start until every fetched array is on the device."""
    fetch = _named(spans, FETCH)
    ns = sum(s.end - s.start for s in fetch)
    return sum(s.stats.get("nbytes", 0) for s in fetch) / ns if ns else None


def plan_ms(spans: List[Span]) -> Optional[float]:
    """Median host time of the coordinator's ``msched.plan``."""
    plans = _named(spans, PLAN)
    return statistics.median(s.end - s.start for s in plans) / 1e6 if plans else None


def step_idle_share(trace: List[dict], spans: List[Span], w0: float, w1: float) -> Optional[float]:
    """Share (%) of the ``msched.step`` spans' time in the window in which
    the first busy TPU ran no operation."""
    busy = _first_busy(trace, w0, w1)
    steps = [(max(s.start, w0), min(s.end, w1)) for s in _named(spans, STEP) if s.end > w0 and s.start < w1]
    total = sum(e - s for s, e in steps)
    if busy is None or not total:
        return None
    starts = [b for b, _ in busy]
    covered = 0.0
    for s, e in steps:
        i = max(0, bisect.bisect_right(starts, s) - 1)
        while i < len(busy) and busy[i][0] < e:
            covered += max(0.0, min(busy[i][1], e) - max(busy[i][0], s))
            i += 1
    return 100.0 * (1.0 - covered / total)


def summarize(trace: List[dict], spans: List[Span], w0: float, w1: float, top: int = 10) -> dict:
    """The window's program-span numbers, with ``idle_by_span`` shaped as the
    result line's ``idle_gaps``: ``["<label> (<n> gaps)", seconds]``, the
    ``top`` largest. Sums and medians count the spans that start in the
    window; a gap or a step is labelled or clipped by any span over it."""
    gaps = idle_by_span(trace, spans, w0, w1)
    idle = step_idle_share(trace, spans, w0, w1)
    spans = in_window(spans, w0, w1)
    return {
        "evict_share": evict_share(spans),
        "h2d_gbps": h2d_gbps(spans),
        "plan_ms": plan_ms(spans),
        "step_idle_share": idle,
        "idle_by_span": [
            [f"{label} ({n} gaps)", ns / 1e9] for label, (n, ns) in sorted(gaps.items(), key=lambda x: -x[1][1])
        ][:top],
        "self_s": {name: ns / 1e9 for name, ns in sorted(self_time(spans).items())},
        "copies": copies(spans),
        "spans": len(spans),
    }
