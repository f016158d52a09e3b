"""Serve three full-width models under 1.5x oversubscription on one TPU chip.

    python chip_smoke.py

Builds a ``MultiModelServer`` over qwen3-1.7b, mamba2-1.3b and minicpm-2b at
their published widths (random weights from fixed seeds, about 11.6 GiB)
with a device pool of two thirds of that, at the platform's 4 MiB extent.
It answers 24 requests, 8 per model, with 4 decode steps per slice, so every
model gets at least two slices and weights are evicted and fetched back.

Every answer's logits must equal, bit for bit, those of the same decode step
run on the same chip with that model alone and all of its weights resident.
The script fails when a request is left unanswered, when no bytes moved in
either direction, when the logits differ, when the device holds more than
the pool budget of weights after a slice, or when JAX finds no TPU. The
last line of its output is a JSON object naming the device.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

ARCHS = ["qwen3-1.7b", "mamba2-1.3b", "minicpm-2b"]
REQUESTS_PER_MODEL = 8
OVERSUB = 1.5
STEPS_PER_SLICE = 4


class SmokeFailure(RuntimeError):
    pass


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def _allocator_bytes(dev):
    """(bytes_in_use, peak_bytes_in_use) from the device allocator, or None
    where the backend keeps no count (the CPU). Counters, not device times."""
    stats = dev.memory_stats()
    return stats and (stats["bytes_in_use"], stats["peak_bytes_in_use"])


def _all_resident_logits(task, steps):
    """Logits of ``steps`` with the task alone and every segment resident."""
    import jax

    for s in task.segments:
        s.device = jax.device_put(s.host)
    out = {i: task.run_step(i) for i in steps}
    for s in task.segments:
        s.device = None
    return out


def run_smoke(
    archs, reduced: bool, page_size: int, oversub: float = OVERSUB, log=print
) -> dict:
    """Build, serve and check; raises ``SmokeFailure`` on any failed check.

    Phases: set-up (init, profiling, and the all-resident reference run,
    which compiles each model's step), then serving the requests through
    the oversubscribed server, then the bit-for-bit comparison. Where the
    allocator keeps counts, the device bytes in use after every served
    slice, less those in use after set-up (no weights resident then), must
    stay within the pool budget."""
    import jax
    import numpy as np

    from repro.runtime.serve_loop import MultiModelServer, Request

    dev = jax.devices()[0]
    t0 = time.perf_counter()
    server = MultiModelServer(
        archs,
        oversub=oversub,
        steps_per_slice=STEPS_PER_SLICE,
        reduced=reduced,
        page_size=page_size,
    )
    rt = server.runtime
    log(
        f"models {archs} reduced={reduced} page_size={page_size} B: "
        f"footprint {server.footprint_bytes} B, pool budget "
        f"{server.budget_bytes} B"
    )
    after_init = _allocator_bytes(dev)
    steps = range(REQUESTS_PER_MODEL)
    reference = {
        tid: _all_resident_logits(task, steps) for tid, task in rt.tasks.items()
    }
    setup_s = time.perf_counter() - t0
    after_setup = _allocator_bytes(dev)

    n_requests = REQUESTS_PER_MODEL * len(archs)
    t1 = time.perf_counter()
    requests = [
        Request(model=i % len(archs), arrival_s=time.perf_counter())
        for i in range(n_requests)
    ]
    for req in requests:
        server.submit(req)
    slice_in_use = []
    stats = server.serve(
        wall_budget_s=900.0,
        on_slice=lambda _: slice_in_use.append(_allocator_bytes(dev)),
    )
    serve_s = time.perf_counter() - t1
    after_serve = _allocator_bytes(dev)

    for tid, task in rt.tasks.items():
        log(
            f"model {tid} {task.cfg.name}: answered {stats.served[tid]}, "
            f"host->device {rt.stats.in_bytes[tid]} B, "
            f"evicted {rt.stats.out_bytes[tid]} B, "
            f"demand faults {rt.stats.faults[tid]}"
        )
    log(f"set-up seconds (host clock, incl. init and compiles): {setup_s}")
    log(f"serve seconds (host clock): {serve_s}")
    serve_max_in_use = None
    if after_setup is not None:
        serve_max_in_use = max(in_use for in_use, _ in slice_in_use)
        for label, (in_use, peak) in (
            ("after init and profiling", after_init),
            ("after set-up", after_setup),
            ("after serving", after_serve),
        ):
            log(f"device bytes_in_use {label}: {in_use}, peak_bytes_in_use {peak}")
        log(
            f"device bytes_in_use after a served slice, largest: "
            f"{serve_max_in_use} ({serve_max_in_use - after_setup[0]} above set-up)"
        )
        _check(
            serve_max_in_use - after_setup[0] <= server.budget_bytes,
            "device bytes in use after a slice exceed the pool budget",
        )

    unanswered = sum(req.logits is None for req in requests)
    _check(not unanswered, f"{unanswered} of {n_requests} requests unanswered")
    _check(stats.migrated_in_bytes > 0, "no bytes moved host->device")
    _check(stats.migrated_out_bytes > 0, "the pool evicted nothing")
    mismatched = []
    for req in requests:
        ref = reference[req.model][req.step]
        got = req.logits
        same = (
            got.shape == ref.shape
            and got.dtype == ref.dtype
            and got.tobytes() == ref.tobytes()
            and bool(np.isfinite(got.astype(np.float32)).all())
        )
        if not same:
            mismatched.append((req.model, req.step))
    _check(
        not mismatched,
        f"logits differ from the all-resident run or are not finite: {mismatched}",
    )
    return {
        "answered": sum(stats.served.values()),
        "migrated_in_bytes": stats.migrated_in_bytes,
        "migrated_out_bytes": stats.migrated_out_bytes,
        "demand_faults": stats.demand_faults,
        "setup_s": setup_s,
        "serve_s": serve_s,
        "serve_max_in_use": serve_max_in_use,
    }


def main() -> int:
    from repro.launch.compile_cache import enable_compile_cache

    # libtpu writes its logs to /tmp/tpu_logs unless told otherwise
    tpu_logs = os.path.join(tempfile.gettempdir(), "tpu_logs")
    os.makedirs(tpu_logs, exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", tpu_logs)
    cache = enable_compile_cache()

    import jax

    from repro.core.hardware import TPU_V5E

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX's first device is {dev.platform}", file=sys.stderr)
        return 1
    print(f"compile cache directory: {cache}")
    out = run_smoke(ARCHS, reduced=False, page_size=TPU_V5E.page_size)
    _check(out["serve_max_in_use"] is not None, "the TPU allocator gave no counts")
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": dev.platform,
                    "kind": dev.device_kind,
                    "count": len(jax.devices()),
                },
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
