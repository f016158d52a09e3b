"""One run of one benchmark cell: build, warm up, drive, check, measure.

``bench/run.py`` checks for the chip and calls ``run_cell``; the tests call it
at the reduced CPU cut. Everything that belongs to one configuration, traffic
mix or metric is found by its name in ``BENCHMARK.json``:

- ``bench/configs/<config>.json``: the models, pool rule and slice length;
- ``bench/traffic/<mix>.json``: the mix's parameters; its ``kind`` names the
  load module ``bench/load/<kind>.py`` that drives it;
- ``bench/metrics/<metric>.py``: a reader ``read(record)`` of one metric,
  which returns None where it finds nothing to read. A metric named
  ``<base>.<variant>`` (one quantity that moves a different end-to-end
  metric in different cells) is read by ``<base>.py`` unless it has a file
  of its own.
- ``bench/families/<model_type>.py``, named by each model's ``model_type``
  in its configuration: the model's parameter layout, reference and step
  cost (``bench/families/__init__.py``).

The window drives the program's serving entry, ``MultiModelServer.submit``
and ``.serve``; the harness submits between and after slices, from the
``on_slice`` callback. As each answer comes back the harness keeps one copy
of each distinct answer to each input and drops the request's logits. Once
the window has closed and the program's device state is freed,
``bench.reference`` recomputes those inputs in float32 from the same seeded
weights, and the run is correct when no kept answer's logits lie further
from the reference's than the configuration's limit (``logit_error``) and
every weight the program holds, on the host and on the device, is the one
the harness made (``bench.integrity``). A traced run also reduces the
program's own ``msched.*`` spans in the window (``bench.spans``), for the
readers in ``TraceSummary.spans``.
"""
from __future__ import annotations

import contextlib
import gc
import glob
import hashlib
import importlib
import importlib.util
import itertools
import json
import shutil
import sys
import tempfile
import time
from collections import defaultdict, deque
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import BenchError, costs, integrity, reference, spans, trace_reduce, weights
from bench.record import Counters, RunRecord, Slice, TraceSummary, Tracked

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
WARMUP_LIMIT_S = 600.0
MAX_VARIANTS = 4  # distinct answers kept per input; more leave the run unchecked
TRACE_WINDOW_S = 10.0  # a traced run traces the window's first seconds
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def step_token(step: int) -> int:
    """The token ``LiveModelTask.run_step`` feeds at the model's decode step
    ``step``: the step's input, which the program chooses."""
    return 1 + step % 13


INPUTS = tuple(step_token(s) for s in range(13))  # one cycle of the program's inputs


# -- finding a cell's files by name --------------------------------------------


def load_cell(root: Path, workload: str) -> dict:
    root = Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json; known: {sorted(cells)}")
    cell = cells[workload]
    (conf,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    e2e = [m for m in bench["end_to_end"] if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    per_layer = [
        m
        for m in bench["per_layer"]
        if (workload in m["workloads"] if "workloads" in m else m["moves"] in reported)
    ]
    return dict(
        cell=cell,
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads((root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text()),
        end_to_end=e2e,
        per_layer=per_layer,
    )


def load_reader(root: Path, name: str):
    metrics = Path(root) / "bench" / "metrics"
    path = metrics / f"{name}.py"
    if not path.exists():
        path = metrics / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- the server under test -----------------------------------------------------


def build_server(cfg: dict, reduced: bool):
    """The configuration's ``MultiModelServer``. Pool rules: ``oversub`` is
    the footprint over the pool; ``whole_segments`` sizes the pool to every
    page-rounded segment of the tasks' own address spaces, plus
    ``spare_pages``."""
    from repro.core.runtime import LiveRuntime
    from repro.runtime.serve_loop import MultiModelServer

    pool = cfg["pool"]
    server = MultiModelServer(
        [m["arch"] for m in cfg["models"]],
        oversub=pool.get("oversub", 1.0),
        steps_per_slice=cfg["steps_per_slice"],
        reduced=reduced,
        page_size=cfg["page_bytes"],
    )
    if pool.get("whole_segments"):
        tasks = list(server.runtime.tasks.values())
        pages = sum(t.space.total_pages() for t in tasks) + pool.get("spare_pages", 0)
        budget = pages * cfg["page_bytes"]
        server.runtime = LiveRuntime(tasks, budget, steps_per_slice=cfg["steps_per_slice"])
        server.budget_bytes = budget
    return server


def install_weights(server, models: List[dict], seeds: List[int]) -> Dict[int, dict]:
    """Write the benchmark's seeded weights into each task's host copies,
    after checking that the program's parameter tree is the layout the
    configuration states. Returns each model's ``host`` and ``device``
    digests of those weights, in segment order (``bench.integrity``)."""
    import jax

    digests = {}
    for i, m in enumerate(models):
        task = server.runtime.tasks[i]
        if task.cfg.name != m["arch"]:
            raise BenchError(f"task {i} serves {task.cfg.name}, the configuration {m['arch']}")
        want = [(p, leaf.shape, leaf.dtype) for p, leaf in weights.leaves_with_paths(weights.layout(m))]
        got = [(s.path, tuple(s.host.shape), str(s.host.dtype)) for s in task.segments]
        if want != got:
            diff = next((w, g) for w, g in itertools.zip_longest(want, got) if w != g)
            raise BenchError(f"{m['arch']}: parameters (configuration, program) differ: {diff}")
        if any(s.device is not None for s in task.segments):
            raise BenchError(f"{m['arch']}: weights resident before the benchmark installed its own")
        leaves = jax.tree.leaves(weights.generate(m, seeds[i]))
        for leaf in leaves:
            leaf.copy_to_host_async()
        digests[i] = {"device": integrity.device_digests(leaves)}
        for seg, leaf in zip(task.segments, leaves):
            seg.host = np.asarray(leaf)
        digests[i]["host"] = integrity.host_digests([s.host for s in task.segments])
        del leaves
    return digests


def release(server) -> None:
    """Drop every device array the program holds, before the reference runs."""
    for task in server.runtime.tasks.values():
        for seg in task.segments:
            seg.device = None
    server.runtime.outputs = {}
    gc.collect()


# -- the harness's side of the serving entry -----------------------------------


class Session:
    """Submits requests, stamps and digests answers, and keeps per-slice
    counters. With ``timing`` it also times each step and writes host spans
    for the trace; with ``trace_dir`` it traces the window."""

    def __init__(self, server, vocab: List[int], timing: bool, trace_dir: Optional[str]):
        self.server = server
        self.clock = time.perf_counter
        self.n_models = len(server.queues)
        self.vocab = vocab
        self.timing = timing
        self.trace_dir = trace_dir
        self.outstanding = {m: deque() for m in server.queues}
        # (model, input token) -> {hash of the logits' bytes: logits}: a sound
        # program answers one input alike every time, so this stays small
        self.answers: Dict[tuple, Dict[bytes, np.ndarray]] = defaultdict(dict)
        self.n_answers = 0
        self.malformed = 0
        self.unchecked = 0
        self.requests: List[Tracked] = []
        self.slices: List[Slice] = []
        self.steps: List[tuple] = []  # (end, seconds) of each timed step
        self.late_s: List[float] = []
        self.t0 = self.t_end = None
        self.base: Optional[Counters] = None
        self.window_compiles = 0
        self._open = False
        self._window = None
        self._serving = None
        self._trace_end = None
        self._hook: Optional[Callable] = None
        self._mark = 0.0
        self._step_acc = 0.0
        if timing:
            for task in server.runtime.tasks.values():
                self._time_steps(task)

    def _time_steps(self, task) -> None:
        """Wrap the task's step: a host span and a host-clock time of each
        step. The step's weights are waited for first, so that copies still
        in flight from the switch count to the switch and not to the step."""
        import jax

        inner, name = task.run_step, f"{trace_reduce.STEP_SPAN}{task.task_id}"

        def run_step(i):
            jax.block_until_ready([s.device for s in task.segments])
            with jax.profiler.TraceAnnotation(name):
                t = self.clock()
                out = inner(i)
                dt = self.clock() - t
            self._step_acc += dt
            self.steps.append((t + dt, dt))
            return out

        task.run_step = run_step

    def _span(self, name: str):
        if not self.timing:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def _serve_span(self, enter: bool) -> None:
        """The host span of ``serve`` between two slice ends (traced runs)."""
        if not self.timing:
            return
        import jax

        if self._serving is not None:
            self._serving.__exit__(None, None, None)
            self._serving = None
        if enter:
            self._serving = jax.profiler.TraceAnnotation("bench.serve")
            self._serving.__enter__()

    def counters(self) -> Counters:
        st = self.server.runtime.stats
        return Counters(sum(st.steps.values()), st.migrated_in_bytes, st.migrated_out_bytes)

    def queued(self) -> bool:
        return any(self.server.queues.values())

    def submit(self, model: int, due: float, in_window: bool) -> Tracked:
        from repro.runtime.serve_loop import Request

        now = self.clock()
        t = Tracked(model, due, now, in_window, Request(model=model, arrival_s=due))
        self.server.submit(t.req)
        self.outstanding[model].append(t)
        if self.t0 is not None:
            self.requests.append(t)
            self.late_s.append(now - due)
        return t

    def _on_compile(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE_EVENT and self._open and self.clock() <= self.t_end:
            self.window_compiles += 1

    def begin(self, seconds: float) -> float:
        """Open the window; returns its start."""
        import jax

        if self.trace_dir:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # it records every Python call and slows the host
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
            self._window = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
            self._window.__enter__()
        self.requests, self.slices, self.steps, self.late_s = [], [], [], []
        self.base = self.counters()
        self._open = True
        jax.monitoring.register_event_duration_secs_listener(self._on_compile)
        self.t0 = self.clock()
        self.t_end = self.t0 + seconds
        self._trace_end = self.t0 + min(seconds, TRACE_WINDOW_S)
        return self.t0

    def _close_if_due(self, now: float) -> None:
        if self._window is not None and now >= self._trace_end:
            import jax

            self._serve_span(enter=False)
            self._window.__exit__(None, None, None)
            self._window = None
            jax.profiler.stop_trace()

    def end(self) -> None:
        """After the load is done: stop the trace and the compile count."""
        import jax

        self._close_if_due(max(self.clock(), self.t_end))
        self._open = False
        jax.monitoring.unregister_event_duration_listener(self._on_compile)

    def serve(self, until: float, hook: Callable) -> None:
        """Serve until the queues drain or ``until``; ``hook(now, answered)``
        runs after every slice."""
        budget = until - self.clock()
        if budget <= 0:
            return
        self._hook = hook
        self._serve_span(enter=True)
        self._mark = self.clock()
        self._step_acc = 0.0
        try:
            self.server.serve(wall_budget_s=budget, on_slice=self._on_slice)
        finally:
            self._serve_span(enter=False)

    def wait(self, until: float) -> None:
        with self._span("bench.wait"):
            time.sleep(max(0.0, until - self.clock()))
        self._close_if_due(self.clock())

    def _on_slice(self, model: int) -> None:
        now = self.clock()
        self._serve_span(enter=False)
        wall, step = now - self._mark, (self._step_acc if self.timing else None)
        self._step_acc = 0.0
        answered = []
        q = self.outstanding[model]
        while q and q[0].req.step is not None:
            t = q.popleft()
            t.done = now
            self._digest(t)
            answered.append(t)
        self.slices.append(Slice(now, model, len(answered), self.counters(), wall, step))
        if self._hook is not None:
            self._hook(now, answered)
        self._close_if_due(now)
        self._serve_span(enter=self._window is not None)
        self._mark = self.clock()

    def _digest(self, t: Tracked) -> None:
        """Keep each distinct answer to each input once, for the check after
        the window, and drop the request's logits."""
        logits, step = t.req.logits, t.req.step
        t.req.logits = None
        self.n_answers += 1
        if step is None or logits is None or tuple(logits.shape) != (1, 1, self.vocab[t.model]):
            self.malformed += 1
            return
        seen = self.answers[(t.model, step_token(step))]
        key = hashlib.blake2b(logits.tobytes(), digest_size=16).digest()
        if key in seen:
            return
        if len(seen) >= MAX_VARIANTS:
            self.unchecked += 1
            return
        seen[key] = np.asarray(logits, np.float32).reshape(-1)

    def warm_up(self) -> None:
        """Switch every model in once and compile every model's step."""
        for m in range(self.n_models):
            t = self.submit(m, self.clock(), False)
            self.serve(self.clock() + WARMUP_LIMIT_S, lambda now, answered: None)
            if t.done is None:
                raise BenchError(f"warm-up request to model {m} unanswered")

    def answered_in_window(self) -> List[Tracked]:
        return [t for t in self.requests if t.done is not None and self.t0 <= t.done <= self.t_end]


# -- the check -------------------------------------------------------------------


def logit_error(served: np.ndarray, ref: np.ndarray) -> float:
    """Largest distance of a served logit from the reference's, in units of
    the reference logits' standard deviation."""
    return float(np.max(np.abs(served - ref)) / np.std(ref))


def compare(
    models: List[dict], seeds: List[int], sess: "Session", limit: float, weights_mismatched: int = 0
) -> Dict:
    """Every distinct answer against the float32 reference of its input.
    ``weights_mismatched`` counts the program's weight copies that differ
    from the harness's (``bench.integrity.mismatched``). While it holds each
    model's seeded weights it also takes the model's step cost over the
    program's inputs (``bench.costs.step_cost``), returned as ``costs``."""
    worst = 0.0
    step_costs = {}
    for i, m in enumerate(models):
        params = weights.generate(m, seeds[i])
        step_costs[i] = costs.step_cost(m, params, INPUTS)
        toks = sorted(tok for model, tok in sess.answers if model == i)
        if not toks:
            continue
        ref = reference.logits(m, params, toks)
        del params
        if not np.isfinite(ref).all():
            raise BenchError(f"{m['arch']}: the reference's logits are not finite")
        for j, tok in enumerate(toks):
            for served in sess.answers[(i, tok)].values():
                err = logit_error(served, ref[j])
                worst = max(worst, err if np.isfinite(err) else float("inf"))
    checks = {
        "logit_error": {"value": worst, "limit": limit},
        "answers_malformed": {"value": sess.malformed, "limit": 0},
        "answers_unchecked": {"value": sess.unchecked, "limit": 0},
        "answers_compared": {"value": sess.n_answers, "limit": 1, "at_least": True},
        "weights_mismatched": {"value": weights_mismatched, "limit": 0},
    }
    correct = (
        worst <= limit
        and sess.malformed == 0
        and sess.unchecked == 0
        and sess.n_answers >= 1
        and weights_mismatched == 0
    )
    return {"correct": correct, "checks": checks, "costs": step_costs}


# -- one run ---------------------------------------------------------------------


def _summarize_trace(trace_dir: str) -> TraceSummary:
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    tr = trace_reduce.load_xplane(path)
    w0, w1 = trace_reduce.window(tr)
    busy = trace_reduce.busy_ns(tr, w0, w1)
    runs = trace_reduce.step_runs(tr, w0, w1)
    gaps = trace_reduce.idle_gaps(tr, w0, w1)
    program = spans.load(path)
    return TraceSummary(
        window_s=(w1 - w0) / 1e9,
        busy_s=None if busy is None else busy / 1e9,
        step_runs={m: (n, ns / 1e9) for m, (n, ns) in runs.items()},
        device_ops=[[name, ns / 1e9] for name, ns in trace_reduce.top_ops(tr, w0, w1)],
        idle_gaps=[
            [f"{label} ({n} gaps)", ns / 1e9]
            for label, (n, ns) in sorted(gaps.items(), key=lambda x: -x[1][1])
        ][:10],
        spans=spans.summarize(tr, program, w0, w1),
    )


def run_cell(
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    t_start: float,
    root: Path = ROOT,
    reduced: bool = False,
    peaks: Optional[Dict[str, float]] = None,
    log=lambda msg: print(msg, file=sys.stderr, flush=True),
) -> dict:
    """Run ``workload`` once and return the result line's object (``checks``,
    the compared numbers with their limits, is its last key)."""
    import jax

    spec = load_cell(root, workload)
    cfg, mix = spec["config"], spec["traffic"]
    models = cfg["models"]
    metrics = spec["per_layer"] if trace else spec["end_to_end"]
    readers = {m["name"]: load_reader(root, m["name"]) for m in metrics}
    load = importlib.import_module(f"bench.load.{mix['kind']}")
    dev = jax.devices()[0]
    peaks = peaks if peaks is not None else costs.peaks(dev.device_kind)
    traffic_rng = np.random.default_rng([seed, 0])
    seeds = [int(s) for s in np.random.default_rng([seed, 1]).integers(0, 2**31, len(models))]

    t = time.perf_counter()
    server = build_server(cfg, reduced)
    log(f"{workload}: footprint {server.footprint_bytes} B, pool budget {server.budget_bytes} B")
    log(f"set-up: program built in {time.perf_counter() - t:.1f} s")
    t = time.perf_counter()
    expected = install_weights(server, models, seeds)
    log(f"set-up: weights made and copied to the host in {time.perf_counter() - t:.1f} s")
    vocab = [m["vocab_size"] for m in models]
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        sess = Session(server, vocab, timing=trace, trace_dir=trace_dir)
        t = time.perf_counter()
        sess.warm_up()
        log(f"set-up: warm-up in {time.perf_counter() - t:.1f} s")
        outcome = load.drive(sess, mix, seconds, traffic_rng)
        stats = dev.memory_stats()
        peak = stats["peak_bytes_in_use"] if stats else 0
        summary = _summarize_trace(trace_dir) if trace else None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    t = time.perf_counter()
    bad = integrity.mismatched(server, expected)
    log(f"weights digested in {time.perf_counter() - t:.1f} s; copies that differ: {bad or 'none'}")
    release(server)
    del server
    sess.server = None
    gc.collect()
    t_ref = time.perf_counter()
    check = compare(models, seeds, sess, cfg["correct"]["logit_error"], len(bad))
    log(f"reference check took {time.perf_counter() - t_ref:.1f} s")

    record = RunRecord(
        models=models,
        seconds=seconds,
        setup_s=sess.t0 - t_start,
        t0=sess.t0,
        t_end=sess.t_end,
        requests=sess.requests,
        outcome=outcome,
        base=sess.base,
        slices=sess.slices,
        step_s=[dt for end, dt in sess.steps if end <= sess.t_end],
        trace=summary,
        costs=check["costs"],
        peaks=peaks,
    )
    out_metrics = {}
    for m in metrics:
        value = readers[m["name"]].read(record)
        if value is not None:
            out_metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": peak,
    }
    result = {
        "correct": check["correct"],
        "attempted": outcome.attempted,
        "failed": outcome.failed,
        "metrics": out_metrics,
        "device": device,
    }
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.device_ops, "idle_gaps": summary.idle_gaps}
    late = sorted(sess.late_s) or [0.0]
    result["load"] = {
        "submitted_late_ms_median": 1000.0 * late[len(late) // 2],
        "submitted_late_ms_max": 1000.0 * late[-1],
        "window_compiles": sess.window_compiles,
        "slices": len(record.window_slices()),
    }
    result["checks"] = check["checks"]
    return result
