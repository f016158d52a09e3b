"""Input specs per (arch × shape): ShapeDtypeStruct specs and the concrete
random batches for smoke tests built from them — one source of truth.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeSpec
from repro.models import common

VISION_FRACTION = 8  # qwen2-vl: 1/8 of the sequence is vision patches


def batch_spec(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, jax.ShapeDtypeStruct]:
    """Abstract input specs for ``train_step``/``prefill_step``/``serve_step``."""
    b, s = shape.global_batch, shape.seq_len
    dt = common.dtype_of(cfg)
    i32 = jnp.int32

    if shape.kind == "decode":
        spec = {"tokens": jax.ShapeDtypeStruct((b, 1), i32)}
        if cfg.family == "vlm":
            pass  # decode positions derive from the cache index
        return spec

    if cfg.family == "audio":
        spec = {
            "frames": jax.ShapeDtypeStruct((b, s, cfg.d_model), dt),
            "frame_mask": jax.ShapeDtypeStruct((b, s), jnp.bool_),
        }
        if shape.kind == "train":
            spec["labels"] = jax.ShapeDtypeStruct((b, s), i32)
        return spec

    if cfg.family == "vlm":
        s_vis = s // VISION_FRACTION
        s_text = s - s_vis
        spec = {
            "tokens": jax.ShapeDtypeStruct((b, s_text), i32),
            "vision_embeds": jax.ShapeDtypeStruct((b, s_vis, cfg.d_model), dt),
            "positions3": jax.ShapeDtypeStruct((3, b, s), i32),
        }
        if shape.kind == "train":
            spec["labels"] = jax.ShapeDtypeStruct((b, s_text), i32)
        return spec

    spec = {"tokens": jax.ShapeDtypeStruct((b, s), i32)}
    if shape.kind == "train":
        spec["labels"] = jax.ShapeDtypeStruct((b, s), i32)
    return spec


def make_concrete_batch(cfg: ModelConfig, shape: ShapeSpec, seed: int = 0):
    """Materialize a random batch matching ``batch_spec`` (smoke tests)."""
    rng = np.random.default_rng(seed)
    out = {}
    for name, sds in batch_spec(cfg, shape).items():
        if name in ("tokens", "labels"):
            out[name] = jnp.asarray(
                rng.integers(1, cfg.vocab_size, size=sds.shape, dtype=np.int32)
            )
        elif name == "positions3":
            _, b, s = sds.shape
            pos = np.broadcast_to(np.arange(s, dtype=np.int32), (3, b, s))
            out[name] = jnp.asarray(pos)
        elif name == "frame_mask":
            out[name] = jnp.asarray(rng.random(sds.shape) < 0.3)
        else:  # float embeddings
            out[name] = jnp.asarray(
                rng.standard_normal(sds.shape), dtype=sds.dtype
            )
    return out
