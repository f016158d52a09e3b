"""Offline kernel profiler (paper §5.2, the NVBit analogue).

Runs workload programs in instrumented mode and records, per kernel, every
invocation's launch arguments, touched extents, and latency into a
``TraceStore``. The memory analyzer then fits the templates offline ("can be
integrated into the compiler or executed during installation").
"""
from __future__ import annotations

from typing import Sequence

from repro.core.trace import TraceStore


def profile_programs(programs: Sequence, iters: int = 4) -> TraceStore:
    """Each program has a ``space`` (its ``AddressSpace``) and
    ``iteration(it)``, the commands of iteration ``it``."""
    store = TraceStore()
    for prog in programs:
        for it in range(iters):
            for cmd in prog.iteration(it):
                store.record(cmd, space=prog.space)
    return store
