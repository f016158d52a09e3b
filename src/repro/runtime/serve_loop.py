"""Batched serving loop with MSched-style multi-model scheduling.

Hosts several models on one device budget: requests queue per model, the
server names the model with the oldest request as the next slice's task,
and the MSched coordinator proactively migrates that model's weights into
the device pool before its batch runs — serving-side integration of the
paper's extended context switch (the live analogue of benchmarks fig13).
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, Deque, Dict, List, Optional

import numpy as np

from repro.core.hardware import TPU_V5E
from repro.core.runtime import LiveModelTask, LiveRuntime


@dataclasses.dataclass
class Request:
    """One request; the server stamps it on ``time.perf_counter``'s clock
    when it is submitted, when the slice that answers it starts and when it
    is answered, and records that slice's serial number (the ``slice`` stat
    of its ``msched.slice`` span)."""

    model: int
    arrival_s: float
    tokens: int = 1
    submitted_s: Optional[float] = None
    # filled in when the request is answered
    started_s: Optional[float] = None
    answered_s: Optional[float] = None
    slice: Optional[int] = None
    step: Optional[int] = None  # the model's decode step that served it
    logits: Optional[np.ndarray] = None


@dataclasses.dataclass
class ServeStats:
    served: Dict[int, int]
    migrated_in_bytes: int
    migrated_out_bytes: int
    demand_faults: int


class MultiModelServer:
    def __init__(
        self,
        archs: List[str],
        oversub: float = 1.5,
        steps_per_slice: int = 4,
        reduced: bool = True,
        page_size: Optional[int] = None,
    ):
        """``oversub`` is the models' summed footprint over the device pool
        budget (1.5 = 150% oversubscription). ``page_size`` defaults to 4 KiB
        for the reduced CPU cut and to the TPU's 4 MiB extent otherwise, which
        keeps a full-width set at thousands of pages, not millions."""
        if page_size is None:
            page_size = 4096 if reduced else TPU_V5E.page_size
        tasks = [
            LiveModelTask(i, a, page_size=page_size, seed=i, reduced=reduced)
            for i, a in enumerate(archs)
        ]
        self.footprint_bytes = sum(t.footprint_bytes() for t in tasks)
        self.budget_bytes = int(self.footprint_bytes / oversub)
        self.runtime = LiveRuntime(
            tasks, self.budget_bytes, steps_per_slice=steps_per_slice
        )
        self.queues: Dict[int, Deque[Request]] = {
            t.task_id: deque() for t in tasks
        }

    def submit(self, req: Request) -> None:
        req.submitted_s = time.perf_counter()
        self.queues[req.model].append(req)

    def serve(
        self,
        wall_budget_s: float = 5.0,
        on_slice: Optional[Callable[[int], None]] = None,
    ) -> ServeStats:
        """Serve queued requests until the queues drain or the wall budget
        runs out; requests still queued then are left in ``self.queues``.
        ``on_slice(model)`` is called after each slice's requests are
        answered."""
        stats = ServeStats({m: 0 for m in self.queues}, 0, 0, 0)
        t_end = time.perf_counter() + wall_budget_s
        rt = self.runtime
        while time.perf_counter() < t_end and any(self.queues.values()):
            # pick the model with the oldest pending request (FIFO fairness)
            pending = {m: q for m, q in self.queues.items() if q}
            model = min(pending, key=lambda m: pending[m][0].arrival_s)
            # run one slice for that model via the MSched runtime
            before = rt.stats.steps[model]
            rt.put_first(model)
            started = time.perf_counter()
            rt.run(total_slices=1)
            now = time.perf_counter()
            outputs = rt.outputs[model]
            for i in range(min(len(outputs), len(self.queues[model]))):
                req = self.queues[model].popleft()
                req.started_s, req.answered_s, req.slice = started, now, rt.last_slice
                req.step = before + i
                req.logits = outputs[i]
                stats.served[model] += 1
            if on_slice is not None:
                on_slice(model)
        stats.migrated_in_bytes = rt.stats.migrated_in_bytes
        stats.migrated_out_bytes = rt.stats.migrated_out_bytes
        stats.demand_faults = rt.stats.demand_faults
        return stats
