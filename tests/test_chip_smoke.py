"""chip_smoke.py's body at the reduced CPU cut: the same three models, the
same requests and checks, so the chip smoke's control flow is exercised on
every run of the suite."""
import importlib.util
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_body_reduced(smoke):
    # each reduced model's 1 MiB KV placeholder outweighs its weights, so at
    # 1.5x every weight would stay resident; 3x forces evictions
    lines = []
    out = smoke.run_smoke(
        smoke.ARCHS, reduced=True, page_size=4096, oversub=3.0, log=lines.append
    )
    assert out["answered"] == smoke.REQUESTS_PER_MODEL * len(smoke.ARCHS)
    assert out["migrated_in_bytes"] > 0 and out["migrated_out_bytes"] > 0
    assert sum(line.startswith("model ") for line in lines) == len(smoke.ARCHS)


def _live_bytes(dev):
    # the CPU allocator keeps no counts; the live arrays stand in for them
    n = sum(a.nbytes for a in jax.live_arrays())
    return n, n


def test_smoke_body_device_bytes_within_budget(smoke, monkeypatch):
    monkeypatch.setattr(smoke, "_allocator_bytes", _live_bytes)
    lines = []
    out = smoke.run_smoke(
        smoke.ARCHS, reduced=True, page_size=4096, oversub=3.0, log=lines.append
    )
    assert out["serve_max_in_use"] > 0
    assert sum("bytes_in_use" in line for line in lines) == 4


def test_smoke_body_fails_over_budget(smoke, monkeypatch):
    # nothing in use until set-up ends, then far more than any pool budget
    calls = []

    def fake(dev):
        calls.append(dev)
        n = 0 if len(calls) <= 2 else 1 << 50
        return n, n

    monkeypatch.setattr(smoke, "_allocator_bytes", fake)
    with pytest.raises(smoke.SmokeFailure, match="exceed the pool budget"):
        smoke.run_smoke(
            smoke.ARCHS, reduced=True, page_size=4096, oversub=3.0, log=[].append
        )


def test_smoke_body_fails_without_evictions(smoke):
    with pytest.raises(smoke.SmokeFailure, match="evicted nothing"):
        smoke.run_smoke(smoke.ARCHS[:1], reduced=True, page_size=4096, log=[].append)


def test_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, SMOKE], env=env, capture_output=True, text=True, timeout=120
    )
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "no TPU" in proc.stderr
