"""Sharding rules: parameter and optimizer-state specs.

Strategy:
  * TP over ``model``: attention heads, FFN hidden, vocab, SSM heads,
    RG-LRU channels; experts over ``model`` (EP) when E >= |model|, else
    per-expert tensor parallelism.
  * FSDP over ``data``: the non-TP dimension of every large matrix (ZeRO-
    style, optimizer state follows parameters).
  * DP over ``(pod, data)``: the global batch; gradients reduce over both.

Rules are path-based over the parameter pytree, so every architecture in the
zoo gets consistent shardings without per-model code.
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig


def _path_str(path) -> str:
    out = []
    for k in path:
        if hasattr(k, "key"):
            out.append(str(k.key))
        elif hasattr(k, "idx"):
            out.append(str(k.idx))
        else:
            out.append(str(k))
    return "/".join(out)


def _param_spec(cfg: ModelConfig, name: str, ndim: int, shape, mesh) -> P:
    """PartitionSpec for one parameter leaf (without the stacked-layer axis —
    callers prepend None for leaves living under 'layers')."""
    model_n = mesh.shape["model"]
    leaf = name.rsplit("/", 1)[-1]

    def fsdp_ok(dim_size) -> bool:
        return dim_size % mesh.shape["data"] == 0

    def tp_ok(dim_size) -> bool:
        return dim_size % model_n == 0

    if leaf == "embed":  # (V, D)
        return P("model" if tp_ok(shape[0]) else None, None)
    if leaf == "lm_head":  # (D, V)
        return P("data" if fsdp_ok(shape[0]) else None, "model" if tp_ok(shape[1]) else None)
    if leaf in ("wq", "wk", "wv", "w1", "w3", "in_proj", "w_in", "w_gate_branch", "w_a", "w_x"):
        return P(
            "data" if fsdp_ok(shape[0]) else None,
            "model" if tp_ok(shape[1]) else None,
        )
    if leaf in ("wo", "w2", "out_proj", "w_out"):
        return P(
            "model" if tp_ok(shape[0]) else None,
            "data" if fsdp_ok(shape[1]) else None,
        )
    if leaf in ("bq", "bk", "bv"):
        return P("model" if tp_ok(shape[0]) else None)
    if leaf == "router":  # (D, E)
        return P("data" if fsdp_ok(shape[0]) else None, None)
    if leaf == "conv_w":  # (W, C)
        return P(None, "model" if tp_ok(shape[1]) else None)
    if leaf in ("conv_b", "gate_norm", "lam"):
        return P("model" if tp_ok(shape[0]) else None)
    if leaf in ("A_log", "D", "dt_bias"):
        return P("model" if tp_ok(shape[0]) else None)
    # moe experts handled by caller (3D); norms and scalars replicate
    return P(*([None] * ndim))


def _moe_expert_spec(cfg: ModelConfig, shape, mesh) -> P:
    """(E, D, F) or (E, F, D): EP over model when E divides, else TP on the
    hidden dim (per-expert tensor parallelism, e.g. grok-1's 8 experts on a
    16-way model axis)."""
    model_n = mesh.shape["model"]
    e, a, b = shape
    if e % model_n == 0:
        return P("model", "data" if a % mesh.shape["data"] == 0 else None, None)
    # hidden dim is whichever of a/b equals moe.d_ff
    dff = cfg.moe.d_ff
    if b == dff:
        return P(None, "data" if a % mesh.shape["data"] == 0 else None, "model" if b % model_n == 0 else None)
    return P(None, "model" if a % model_n == 0 else None, "data" if b % mesh.shape["data"] == 0 else None)


def param_shardings(cfg: ModelConfig, params_shape: Any, mesh) -> Any:
    """NamedSharding tree matching the (abstract) params tree."""

    def leaf_spec(path, leaf):
        name = _path_str(path)
        shape = leaf.shape
        stacked = (
            "layers/" in name
            or name.startswith("layers")
            or "rec_layers" in name
            or "attn_layers" in name
        )
        if stacked:
            inner_shape = shape[1:]
        else:
            inner_shape = shape
        lname = name.rsplit("/", 1)[-1]
        if cfg.moe is not None and lname in ("w1", "w2", "w3") and len(inner_shape) == 3:
            spec = _moe_expert_spec(cfg, inner_shape, mesh)
        else:
            spec = _param_spec(cfg, name, len(inner_shape), inner_shape, mesh)
        if stacked:
            spec = P(None, *spec)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(leaf_spec, params_shape)


def opt_state_shardings(cfg: ModelConfig, opt_shape: Any, pspecs: Any, mesh) -> Any:
    """Optimizer state follows parameters (AdamW m/v mirror; Adafactor
    factored moments drop the corresponding axis)."""
    flat_params, _ = jax.tree_util.tree_flatten(pspecs)

    # adamw: {'m': tree, 'v': tree, 'count': scalar}
    def build(node_shape, node_spec):
        return node_spec

    if isinstance(opt_shape, dict) and "m" in opt_shape:
        return {
            "m": pspecs,
            "v": pspecs,
            "count": NamedSharding(mesh, P()),
        }
    if isinstance(opt_shape, dict) and "state" in opt_shape:
        # adafactor: per-leaf {'vr','vc'} or {'v'}
        def leaf_state(param_spec_leaf, state_leaf):
            spec = param_spec_leaf.spec
            if isinstance(state_leaf, dict) and "vr" in state_leaf:
                return {
                    "vr": NamedSharding(mesh, P(*spec[:-1])),
                    "vc": NamedSharding(mesh, P(*(tuple(spec[:-2]) + (spec[-1],)))),
                }
            return {"v": param_spec_leaf}

        state = jax.tree.map(
            leaf_state,
            pspecs,
            opt_shape["state"],
            is_leaf=lambda x: isinstance(x, dict) and ("vr" in x or "v" in x),
        )
        return {"state": state, "count": NamedSharding(mesh, P())}
    if isinstance(opt_shape, dict) and "mu" in opt_shape:
        return {"mu": pspecs}
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), opt_shape)
