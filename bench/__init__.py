"""On-chip benchmark of the multi-model server: see ``bench/run.py``."""


class BenchError(RuntimeError):
    """A cell, configuration or program that the benchmark cannot run."""
