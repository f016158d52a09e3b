"""Parameter layout of each served model, and its seeded random weights.

The benchmark makes the weights itself, from ``--seed``, and writes them into
the program's host copies; the reference (``bench/reference.py``) makes them
again from the same seed after the window. So the reference takes nothing
that the program made. The layout is the program's parameter tree (leaf
names, shapes, dtypes), derived here from the configuration file's sizes;
the harness refuses a program whose tree differs from it.

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

DENSE_TYPES = ("qwen3", "minicpm")
SSM_TYPES = ("mamba2",)


@dataclasses.dataclass(frozen=True)
class Leaf:
    """One parameter: shape, dtype name and how it is drawn."""

    shape: Tuple[int, ...]
    dtype: str
    kind: str

    @property
    def nbytes(self) -> int:
        return math.prod(self.shape) * jnp.dtype(self.dtype).itemsize


def dense_dims(m: dict) -> dict:
    d = m["hidden_size"]
    h = m["num_attention_heads"]
    return dict(
        d=d,
        layers=m["num_hidden_layers"],
        heads=h,
        kv_heads=m["num_key_value_heads"],
        head_dim=m.get("head_dim") or d // h,
        ff=m["intermediate_size"],
        vocab=m["vocab_size"],
        tied=m["tie_word_embeddings"],
        eps=m["rms_norm_eps"],
    )


def ssm_dims(m: dict) -> dict:
    s = m["ssm_cfg"]
    d = m["d_model"]
    d_inner = s["expand"] * d
    n_heads = d_inner // s["headdim"]
    groups_state = s["ngroups"] * s["d_state"]
    return dict(
        d=d,
        layers=m["n_layer"],
        vocab=m["vocab_size"],
        tied=m["tie_embeddings"],
        eps=m["norm_epsilon"],
        d_inner=d_inner,
        n_heads=n_heads,
        head_dim=s["headdim"],
        state=groups_state,
        conv_width=s["d_conv"],
        conv_dim=d_inner + 2 * groups_state,
        proj_out=2 * d_inner + 2 * groups_state + n_heads,
    )


def serve_dtype(m: dict) -> str:
    return {"bfloat16": "bfloat16", "float32": "float32"}[m["torch_dtype"]]


def layout(m: dict) -> Dict:
    """The parameter tree of model entry ``m`` as nested dicts of ``Leaf``."""
    t = m["model_type"]
    w = serve_dtype(m)
    f32 = "float32"
    if t in DENSE_TYPES:
        k = dense_dims(m)
        L, d, hd = k["layers"], k["d"], k["head_dim"]
        q, kv = k["heads"] * hd, k["kv_heads"] * hd
        head = {"embed": Leaf((k["vocab"], d), w, "embed"), "final_norm": Leaf((d,), f32, "norm")}
        if not k["tied"]:
            head["lm_head"] = Leaf((d, k["vocab"]), w, "dense")
        attn = {
            "wq": Leaf((L, d, q), w, "dense"),
            "wk": Leaf((L, d, kv), w, "dense"),
            "wv": Leaf((L, d, kv), w, "dense"),
            "wo": Leaf((L, q, d), w, "dense"),
        }
        if t == "qwen3":  # RMSNorm on each head's q and k
            attn["q_norm"] = Leaf((L, hd), f32, "norm")
            attn["k_norm"] = Leaf((L, hd), f32, "norm")
        layers = {
            "attn_norm": Leaf((L, d), f32, "norm"),
            "attn": attn,
            "mlp_norm": Leaf((L, d), f32, "norm"),
            "mlp": {
                "w1": Leaf((L, d, k["ff"]), w, "dense"),
                "w3": Leaf((L, d, k["ff"]), w, "dense"),
                "w2": Leaf((L, k["ff"], d), w, "dense"),
            },
        }
        return {"head": head, "layers": layers}
    if t in SSM_TYPES:
        k = ssm_dims(m)
        L, d = k["layers"], k["d"]
        head = {"embed": Leaf((k["vocab"], d), w, "embed"), "final_norm": Leaf((d,), f32, "norm")}
        if not k["tied"]:
            head["lm_head"] = Leaf((d, k["vocab"]), w, "dense")
        mixer = {
            "in_proj": Leaf((L, d, k["proj_out"]), w, "dense"),
            "conv_w": Leaf((L, k["conv_width"], k["conv_dim"]), w, "dense"),
            "conv_b": Leaf((L, k["conv_dim"]), w, "small"),
            "A_log": Leaf((L, k["n_heads"]), f32, "a_log"),
            "D": Leaf((L, k["n_heads"]), f32, "skip"),
            "dt_bias": Leaf((L, k["n_heads"]), f32, "dt_bias"),
            "gate_norm": Leaf((L, k["d_inner"]), f32, "norm"),
            "out_proj": Leaf((L, k["d_inner"], d), w, "dense"),
        }
        return {"head": head, "layers": {"norm": Leaf((L, d), f32, "norm"), "mixer": mixer}}
    raise ValueError(f"unknown model_type {t!r}")


def leaves_with_paths(tree):
    """[(path string, Leaf)] in the order ``jax.tree.leaves`` gives."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [("/".join(str(k) for k in path), leaf) for path, leaf in flat]


def _draw(key, leaf: Leaf):
    shape = leaf.shape
    normal = lambda: jax.random.normal(key, shape, jnp.float32)  # noqa: E731
    if leaf.kind == "dense":  # fan-in scaled: the contraction axis is -2
        x = normal() / math.sqrt(shape[-2])
    elif leaf.kind == "embed":
        x = normal() * 0.02
    elif leaf.kind == "norm":
        x = normal() * 0.1
    elif leaf.kind == "small":
        x = normal() * 0.02
    elif leaf.kind == "skip":
        x = 1.0 + normal() * 0.1
    elif leaf.kind == "a_log":  # Mamba2's init: A uniform in [1, 16]
        x = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    elif leaf.kind == "dt_bias":  # Mamba2's init: softplus(dt_bias) log-uniform in [1e-3, 1e-1]
        dt = jnp.exp(
            jax.random.uniform(key, shape, jnp.float32, math.log(1e-3), math.log(1e-1))
        )
        x = dt + jnp.log(-jnp.expm1(-dt))
    else:
        raise ValueError(f"unknown leaf kind {leaf.kind!r}")
    return x.astype(leaf.dtype)


def make_params(m: dict, key):
    """All of model ``m``'s weights from ``key`` (trace under ``jax.jit``)."""
    tree = layout(m)
    flat, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(key, len(flat))
    return jax.tree_util.tree_unflatten(
        treedef, [_draw(k, leaf) for k, leaf in zip(keys, flat)]
    )


@functools.lru_cache(maxsize=None)
def _generator(model_json: str):
    m = json.loads(model_json)
    return jax.jit(lambda key: make_params(m, key))


def generate(m: dict, seed: int):
    """Model ``m``'s weights for ``seed`` (0 <= seed < 2**31), made on the
    default device in one jitted call, in the dtypes they are served in."""
    return _generator(json.dumps(m, sort_keys=True))(jax.random.PRNGKey(seed))
