"""The ``qwen3`` family: a dense decoder (``bench/families/dense.py``) with an
RMSNorm on each head's q and k."""
from bench.families import dense
from bench.families.dense import logits, reduce  # noqa: F401


def layout(m: dict) -> dict:
    return dense.layout(m, qk_norm=True)
