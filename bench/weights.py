"""Parameter layout of each served model, and its seeded random weights.

The benchmark makes the weights itself, from ``--seed``, and writes them into
the program's host copies; the reference (``bench/reference.py``) makes them
again from the same seed after the window. So the reference takes nothing
that the program made. The layout is the program's parameter tree (leaf
names, shapes, dtypes), derived from the configuration file's sizes by the
model's family (``bench/families/<model_type>.py``); the harness refuses a
program whose tree differs from it.

Norm weights are stored as offsets from 1 (the layer scales by ``1 + w``),
as the program stores them.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from bench import BenchError, families


@dataclasses.dataclass(frozen=True)
class Leaf:
    """One parameter: shape, dtype name and how it is drawn."""

    shape: Tuple[int, ...]
    dtype: str
    kind: str

    @property
    def nbytes(self) -> int:
        return math.prod(self.shape) * jnp.dtype(self.dtype).itemsize


def serve_dtype(m: dict) -> str:
    return {"bfloat16": "bfloat16", "float32": "float32"}[m["torch_dtype"]]


def normal(key, shape):
    return jax.random.normal(key, shape, jnp.float32)


# leaf kind -> draw(key, shape) in float32; a family adds its own kinds
DRAWS = {
    "dense": lambda key, shape: normal(key, shape) / math.sqrt(shape[-2]),  # fan-in: the contraction axis is -2
    "embed": lambda key, shape: normal(key, shape) * 0.02,
    "norm": lambda key, shape: normal(key, shape) * 0.1,
    "small": lambda key, shape: normal(key, shape) * 0.02,
}


def layout(m: dict) -> Dict:
    """The parameter tree of model entry ``m`` as nested dicts of ``Leaf``."""
    return families.load(m["model_type"]).layout(m)


def leaves_with_paths(tree):
    """[(path string, Leaf)] in the order ``jax.tree.leaves`` gives."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [("/".join(str(k) for k in path), leaf) for path, leaf in flat]


def make_params(m: dict, key):
    """All of model ``m``'s weights from ``key`` (trace under ``jax.jit``)."""
    family = families.load(m["model_type"])
    draws = {**DRAWS, **getattr(family, "draws", {})}
    flat, treedef = jax.tree_util.tree_flatten(family.layout(m))
    unknown = sorted({leaf.kind for leaf in flat} - set(draws))
    if unknown:
        raise BenchError(f"{m['model_type']}: no draw for leaf kinds {unknown}")
    keys = jax.random.split(key, len(flat))
    return jax.tree_util.tree_unflatten(
        treedef, [draws[leaf.kind](k, leaf.shape).astype(leaf.dtype) for k, leaf in zip(keys, flat)]
    )


@functools.lru_cache(maxsize=None)
def _generator(model_json: str):
    m = json.loads(model_json)
    return jax.jit(lambda key: make_params(m, key))


def generate(m: dict, seed: int):
    """Model ``m``'s weights for ``seed`` (0 <= seed < 2**31), made on the
    default device in one jitted call, in the dtypes they are served in."""
    return _generator(json.dumps(m, sort_keys=True))(jax.random.PRNGKey(seed))
