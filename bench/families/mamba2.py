"""Mamba2: a stack of SSD mixers (Dao and Gu, 2024), each behind an RMSNorm.

Every step reads every weight once (no ``step_cost``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench import reference, weights
from bench.reference import F32, _mm, _rms, _w
from bench.weights import Leaf


def dims(m: dict) -> dict:
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


def _dt_bias(key, shape):
    """Mamba2's init: softplus(dt_bias) log-uniform in [1e-3, 1e-1]."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


draws = {
    "skip": lambda key, shape: 1.0 + weights.normal(key, shape) * 0.1,
    # Mamba2's init: A uniform in [1, 16]
    "a_log": lambda key, shape: jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)),
    "dt_bias": _dt_bias,
}


def layout(m: dict) -> dict:
    k = dims(m)
    w, f32 = weights.serve_dtype(m), "float32"
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


def _layer(m, precision, lp, x):
    k = dims(m)
    n = x.shape[0]
    di, ns, nh, hd = k["d_inner"], k["state"], k["n_heads"], k["head_dim"]
    p = lp["mixer"]
    h = _rms(x, lp["norm"], k["eps"])
    proj = _mm(h, _w(p["in_proj"], precision))
    z, xbc, dt = proj[:, :di], proj[:, di : 2 * di + 2 * ns], proj[:, 2 * di + 2 * ns :]
    # causal depthwise conv at the first position: only the last tap sees data
    conv_w = _w(p["conv_w"], precision)
    xbc = jax.nn.silu(xbc * conv_w[-1] + p["conv_b"].astype(F32))
    xs, B, C = xbc[:, :di], xbc[:, di : di + ns], xbc[:, di + ns :]
    dt = jax.nn.softplus(dt + p["dt_bias"])  # (n, heads)
    xh = xs.reshape(n, nh, hd)
    # SSD from a zero state: state = dt * x B^T (its decay exp(dt A) multiplies
    # zero), y = C . state + D x
    cb = jnp.sum(C * B, -1)  # (n,)
    y = cb[:, None, None] * dt[:, :, None] * xh + p["D"][None, :, None] * xh
    y = y.reshape(n, di) * jax.nn.silu(z)
    y = _rms(y, p["gate_norm"], k["eps"])
    return x + _mm(y, _w(p["out_proj"], precision))


def _final(m, precision, head, x):
    return reference.unembed(precision, head, _rms(x, head["final_norm"], dims(m)["eps"]))


def logits(m: dict, params, tokens, precision: str = "float32"):
    x = reference.apply(reference.embed, m, precision, params["head"], tokens)
    x = reference.layers(_layer, m, precision, params["layers"], x)
    return reference.apply(_final, m, precision, params["head"], x)


def reduce(m: dict) -> dict:
    from repro.configs import get_config

    c = get_config(m["arch"]).reduced()
    s = c.ssm
    m = dict(m, vocab_size=c.vocab_size, d_model=c.d_model, n_layer=c.num_layers)
    m["ssm_cfg"] = dict(m["ssm_cfg"], d_state=s.state_dim, d_conv=s.conv_width, expand=s.expand,
                        headdim=s.head_dim, chunk_size=s.chunk)
    return m
