"""What one run leaves for the metric readers in ``bench/metrics/``.

All times are seconds on ``time.perf_counter``'s clock. The window is
[t0, t_end]; ``requests`` holds the requests of the window's load (not the
warm-up's), ``slices`` one entry per slice served from the window's start.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import numpy as np


def percentile(sorted_xs: Sequence[float], pct: float) -> float:
    """Nearest rank over a sorted sample: index floor(pct/100 * n), clamped
    to the last element (the convention of ``core.simulator.percentile``)."""
    if not 0.0 <= pct <= 100.0:
        raise ValueError(f"percentile out of range: {pct}")
    if not sorted_xs:
        raise ValueError("percentile of an empty sample")
    return sorted_xs[min(len(sorted_xs) - 1, int(pct / 100.0 * len(sorted_xs)))]


@dataclasses.dataclass
class Tracked:
    """One request of the load: when it was due, submitted and answered."""

    model: int
    due: float
    submitted: float
    in_window: bool
    req: object  # the program's Request
    done: Optional[float] = None


@dataclasses.dataclass
class Counters:
    """Cumulative runtime counters, summed over models."""

    steps: int
    in_bytes: int
    out_bytes: int


@dataclasses.dataclass
class Slice:
    """One served slice: its end, model, answers, the counters after it, its
    host time since the harness last handed control back, and the part of
    that spent in the model's steps (traced runs only)."""

    t: float
    model: int
    answered: int
    counters: Counters
    wall_s: float
    step_s: Optional[float]


@dataclasses.dataclass
class Outcome:
    """What a load kind reports: requests it counts as attempted and failed,
    and the latency of each attempted request (open loops only; a failed
    request's latency runs to the end of the drain)."""

    attempted: int
    failed: int
    latencies_s: Optional[List[float]] = None


@dataclasses.dataclass
class TraceSummary:
    """The traced window as ``bench.trace_reduce`` reads it, and the
    program's own spans in it as ``bench.spans.summarize`` reduces them."""

    window_s: float
    busy_s: float
    # model index -> (step programs run, their device seconds)
    step_runs: Dict[int, tuple]
    device_ops: list
    idle_gaps: list
    spans: dict


@dataclasses.dataclass
class RunRecord:
    models: List[dict]
    seconds: float
    setup_s: float
    t0: float
    t_end: float
    requests: List[Tracked]
    outcome: Outcome
    base: Counters
    slices: List[Slice]
    step_s: List[float]
    trace: Optional[TraceSummary]
    costs: Dict[int, tuple]  # model index -> mean (FLOPs, bytes) of one step (bench.costs)
    peaks: Dict[str, float]

    def answered_in_window(self) -> List[Tracked]:
        return [t for t in self.requests if t.done is not None and self.t0 <= t.done <= self.t_end]

    def window_slices(self) -> List[Slice]:
        return [s for s in self.slices if s.t <= self.t_end]

    def per_answer(self, field: str) -> Optional[float]:
        """A counter's growth over the window's slices, per answer in them."""
        s = self.window_slices()
        answers = sum(x.answered for x in s)
        if not answers:
            return None
        return (getattr(s[-1].counters, field) - getattr(self.base, field)) / answers

    def switch_share(self) -> Optional[float]:
        """Share (%) of the window's serve time outside the model's steps."""
        s = [x for x in self.window_slices() if x.step_s is not None]
        wall = sum(x.wall_s for x in s)
        if not wall:
            return None
        return 100.0 * (wall - sum(x.step_s for x in s)) / wall

    def latency_ms(self, pct: float) -> Optional[float]:
        lat = self.outcome.latencies_s
        if not lat:
            return None
        return 1000.0 * percentile(sorted(lat), pct)

    def span_number(self, name: str) -> Optional[float]:
        """One of ``bench.spans.summarize``'s numbers of the traced window;
        None untraced, or where the spans hold nothing to read."""
        return None if self.trace is None else self.trace.spans.get(name)

    def median_step_ms(self) -> Optional[float]:
        return 1000.0 * float(np.median(self.step_s)) if self.step_s else None
