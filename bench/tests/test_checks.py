"""The correctness check fails a run whose timed path is broken underneath,
and the float8 control, at the reduced CPU cut."""
import time

import numpy as np
import pytest

from bench import control, harness
from bench.tests.support import CPU_PEAKS, REDUCED_LIMIT, reduce_model, ROOT

CELL = "trio-1.5x.zipf-closed128"  # weights are evicted and fetched back


def _run(root):
    return harness.run_cell(CELL, 2**32 + 3, 1.0, False, time.perf_counter(),
                            root=root, reduced=True, peaks=CPU_PEAKS)


def test_sound_run_is_correct(reduced_root):
    r = _run(reduced_root)
    assert r["correct"] is True
    assert 0 <= r["checks"]["logit_error"]["value"] <= REDUCED_LIMIT
    assert r["checks"]["weights_mismatched"]["value"] == 0


def test_answer_altered_where_produced(reduced_root, monkeypatch):
    from repro.core.runtime import LiveModelTask

    run_step = LiveModelTask.run_step

    def altered(self, i):
        out = run_step(self, i)
        if i % 5 == 2:
            out = out.copy()
            out[..., 0] = out.max() + 1  # token 0 now ranks first
        return out

    monkeypatch.setattr(LiveModelTask, "run_step", altered)
    r = _run(reduced_root)
    assert r["correct"] is False
    assert r["checks"]["logit_error"]["value"] > REDUCED_LIMIT


def test_weights_not_fetched_back_intact(reduced_root, monkeypatch):
    from repro.core.runtime import LiveRuntime

    sync = LiveRuntime._sync_residency

    def lossy(self):
        resident = [s for t in self.tasks.values() for s in t.segments if s.device is not None]
        sync(self)
        for s in resident:
            if s.device is None and s.host.ndim >= 2:  # evicted: its copy is lost
                s.host = np.zeros_like(s.host)

    monkeypatch.setattr(LiveRuntime, "_sync_residency", lossy)
    r = _run(reduced_root)
    assert r["correct"] is False


def _altered_on_eviction(sync, suffix):
    """Each evicted ``suffix`` leaf's host copy gets one value changed."""

    def lossy(self):
        resident = [s for t in self.tasks.values() for s in t.segments if s.device is not None]
        sync(self)
        for s in resident:
            if s.device is None and s.path.endswith(suffix):
                h = s.host.copy().reshape(-1)
                h[h.size // 2] = h[h.size // 2] * 2 + 1
                s.host = h.reshape(s.host.shape)

    return lossy


def _stale_on_fetch(sync, suffix):
    """Each fetched ``suffix`` leaf comes back with its first layer zeroed,
    as a page left stale would leave it."""

    def stale(self):
        missing = [s for t in self.tasks.values() for s in t.segments if s.device is None]
        sync(self)
        for s in missing:
            if s.device is not None and s.path.endswith(suffix):
                s.device = s.device.at[0].set(0)

    return stale


@pytest.mark.parametrize("fault,suffix", [(_altered_on_eviction, "['attn']/['wq']"), (_stale_on_fetch, "['attn']/['wk']")])
def test_weights_the_logits_cannot_see(reduced_root, monkeypatch, fault, suffix):
    """With one key the attention's query and key weights never reach the
    logits; the digests of the weights catch them altered."""
    from repro.core.runtime import LiveRuntime

    monkeypatch.setattr(LiveRuntime, "_sync_residency", fault(LiveRuntime._sync_residency, suffix))
    r = _run(reduced_root)
    assert r["correct"] is False
    assert r["checks"]["weights_mismatched"]["value"] >= 1
    assert r["checks"]["logit_error"]["value"] <= REDUCED_LIMIT


def test_answer_of_another_step(reduced_root, monkeypatch):
    from repro.core.runtime import LiveRuntime

    run = LiveRuntime.run

    def shifted(self, total_slices=12):
        stats = run(self, total_slices)
        for tid, outs in self.outputs.items():
            self.outputs[tid] = outs[1:] + outs[:1]
        return stats

    monkeypatch.setattr(LiveRuntime, "run", shifted)
    r = _run(reduced_root)
    assert r["correct"] is False


def test_logit_error_in_reference_std():
    ref = np.array([0.0, 2.0, -2.0, 0.0])  # std 1.414...
    assert harness.logit_error(ref + 0.1, ref) == pytest.approx(0.1 / ref.std())
    assert harness.logit_error(ref, ref) == 0.0


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_float8_control_fails_the_limit(seed):
    import json

    models = [reduce_model(m) for m in json.loads((ROOT / "bench/configs/trio-1.5x.json").read_text())["models"]]
    seeds = [int(s) for s in np.random.default_rng([seed, 1]).integers(0, 2**31, len(models))]
    tokens = {i: set(range(1, 14)) for i in range(len(models))}
    r = harness.compare(models, seeds, control.control_answers(models, seeds, tokens), REDUCED_LIMIT)
    assert r["correct"] is False
    assert r["checks"]["logit_error"]["value"] > REDUCED_LIMIT
