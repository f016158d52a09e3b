"""Fixtures of the benchmark's tests."""
import pytest

from bench.tests.support import make_reduced_root


@pytest.fixture(scope="session")
def reduced_root(tmp_path_factory):
    return make_reduced_root(tmp_path_factory.mktemp("bench_root"))

