"""The ``minicpm`` family: a dense decoder (``bench/families/dense.py``)."""
from bench.families.dense import layout, logits, reduce  # noqa: F401
