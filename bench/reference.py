"""Plain float32 reference of the served models' one-token step.

Each request the server answers is one decode step of one model over one
token at position 0, with no earlier context: the logits of a sequence of
length 1. This module computes the same logits in straightforward
``jax.numpy``, in float32 with every matrix product at ``HIGHEST``
precision, layer by layer, from the layer equations of each architecture as
published (Qwen3, MiniCPM, Mamba2) and the sizes in the configuration file.
It imports nothing of the program; its weights come from ``bench.weights``.

``precision="fp8"`` is the control: the same reference with every weight
matrix rounded to float8 (e4m3, one scale per matrix), the step below the
bfloat16 the configurations serve in. The benchmark's runs never use it.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

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


def _dense_layer(m, precision, lp, x):
    k = weights.dense_dims(m)
    n = x.shape[0]
    H, G, hd, eps = k["heads"], k["kv_heads"], k["head_dim"], k["eps"]
    # MiniCPM scales each residual branch by scale_depth / sqrt(layers)
    branch = m.get("scale_depth", math.sqrt(k["layers"])) / math.sqrt(k["layers"])
    a = lp["attn"]
    h = _rms(x, lp["attn_norm"], eps)
    q = _mm(h, _w(a["wq"], precision)).reshape(n, H, hd)
    kk = _mm(h, _w(a["wk"], precision)).reshape(n, G, hd)
    v = _mm(h, _w(a["wv"], precision)).reshape(n, G, hd)
    if "q_norm" in a:
        q = _rms(q, a["q_norm"], eps)
        kk = _rms(kk, a["k_norm"], eps)
    q = _rope(q, 0.0, m["rope_theta"])
    kk = _rope(kk, 0.0, m["rope_theta"])
    # query head i reads key/value head i // (H / G); the only key is itself
    kk = jnp.repeat(kk, H // G, axis=1)
    v = jnp.repeat(v, H // G, axis=1)
    scores = jnp.sum(q * kk, -1, keepdims=True) / math.sqrt(hd)  # (n, H, 1)
    probs = jax.nn.softmax(scores, axis=-1)
    o = (probs * v).reshape(n, H * hd)
    x = x + branch * _mm(o, _w(a["wo"], precision))
    h = _rms(x, lp["mlp_norm"], eps)
    f = lp["mlp"]
    up = jax.nn.silu(_mm(h, _w(f["w1"], precision))) * _mm(h, _w(f["w3"], precision))
    return x + branch * _mm(up, _w(f["w2"], precision))


def _ssm_layer(m, precision, lp, x):
    k = weights.ssm_dims(m)
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


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer(model_json, precision, layers, i, x):
    m = json.loads(model_json)
    lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), layers)
    if m["model_type"] in weights.DENSE_TYPES:
        return _dense_layer(m, precision, lp, x)
    return _ssm_layer(m, precision, lp, x)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _embed(model_json, precision, head, tokens):
    m = json.loads(model_json)
    return _w(head["embed"], precision)[tokens] * m.get("scale_emb", 1.0)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head(model_json, precision, head, x):
    m = json.loads(model_json)
    if m["model_type"] in weights.DENSE_TYPES:
        k = weights.dense_dims(m)
        # MiniCPM divides the last hidden state by hidden_size / dim_model_base
        x = _rms(x, head["final_norm"], k["eps"]) / (k["d"] / m.get("dim_model_base", k["d"]))
    else:
        k = weights.ssm_dims(m)
        x = _rms(x, head["final_norm"], k["eps"])
    if k["tied"]:
        return _mm(x, _w(head["embed"], precision).T)
    return _mm(x, _w(head["lm_head"], precision))


def num_layers(m: dict) -> int:
    return m["num_hidden_layers"] if m["model_type"] in weights.DENSE_TYPES else m["n_layer"]


def logits(m: dict, params, tokens, precision: str = "float32") -> np.ndarray:
    """Logits (len(tokens), vocab) of one-token sequences, as float32 numpy."""
    mj = json.dumps(m, sort_keys=True)
    x = _embed(mj, precision, params["head"], jnp.asarray(tokens, jnp.int32))
    for i in range(num_layers(m)):
        x = _layer(mj, precision, params["layers"], i, x)
    return np.asarray(_head(mj, precision, params["head"], x))
