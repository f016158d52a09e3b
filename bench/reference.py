"""Plain float32 reference of the served models' one-token step.

Each request the server answers is one decode step of one model over one
token at position 0, with no earlier context: the logits of a sequence of
length 1. The model's family (``bench/families/<model_type>.py``) computes
the same logits in straightforward ``jax.numpy``, in float32 with every
matrix product at ``HIGHEST`` precision, layer by layer, from the layer
equations of the architecture as published and the sizes in the
configuration file, with the helpers here. It imports nothing of the
program; its weights come from ``bench.weights``.

``precision="fp8"`` is the control: the same reference with every weight
matrix rounded to float8 (e4m3, one scale per matrix), the step below the
bfloat16 the configurations serve in. The benchmark's runs never use it.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from bench import families

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0  # largest finite float8_e4m3fn


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _w(w, precision: str):
    """A weight matrix in float32, or rounded through float8 for the control."""
    w = w.astype(F32)
    if precision == "fp8":
        s = jnp.max(jnp.abs(w)) / FP8_MAX
        s = jnp.where(s > 0, s, 1.0)
        w = (w / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    elif precision != "float32":
        raise ValueError(f"unknown precision {precision!r}")
    return w


def _rms(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w.astype(F32))


def _rope(x, position, theta):
    """Rotate-half RoPE of x (n, heads, head_dim) at one position."""
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = position * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def embed(m, precision, head, tokens):
    """The tokens' rows of ``head["embed"]``, times MiniCPM's ``scale_emb``."""
    return _w(head["embed"], precision)[tokens] * m.get("scale_emb", 1.0)


def unembed(precision, head, x):
    """x times the output head: ``lm_head``, or the tied embedding."""
    if "lm_head" in head:
        return _mm(x, _w(head["lm_head"], precision))
    return _mm(x, _w(head["embed"], precision).T)


def _key(m: dict) -> str:
    return json.dumps(m, sort_keys=True)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _apply(fn, model_json, precision, *args):
    return fn(json.loads(model_json), precision, *args)


def apply(fn, m: dict, precision: str, *args):
    """``fn(m, precision, *args)`` as one jitted call (``fn`` a module-level
    function)."""
    return _apply(fn, _key(m), precision, *args)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _layer(fn, model_json, precision, stack, i, x):
    lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), stack)
    return fn(json.loads(model_json), precision, lp, x)


def layers(fn, m: dict, precision: str, stack, x):
    """x through every layer of ``stack`` (a tree whose leaves lead with the
    layer axis), ``fn(m, precision, layer_params, x)`` one jitted call per
    layer, so that one layer's float32 weights are held at a time."""
    mj = _key(m)
    for i in range(jax.tree.leaves(stack)[0].shape[0]):
        x = _layer(fn, mj, precision, stack, i, x)
    return x


def logits(m: dict, params, tokens, precision: str = "float32") -> np.ndarray:
    """Logits (len(tokens), vocab) of one-token sequences, as float32 numpy."""
    family = families.load(m["model_type"])
    return np.asarray(family.logits(m, params, jnp.asarray(tokens, jnp.int32), precision))
