"""Dense decoders: Qwen3 (RMSNorm on each head's q and k) and MiniCPM (muP
scales of the embedding, the residual branches and the logits).

``bench/families/qwen3.py`` (with ``qk_norm``) and ``minicpm.py`` are this
module. Every step reads every weight once (no ``step_cost``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench import reference, weights
from bench.reference import _mm, _rms, _rope, _w
from bench.weights import Leaf


def dims(m: dict) -> dict:
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


def layout(m: dict, qk_norm: bool = False) -> dict:
    """``qk_norm``: an RMSNorm on each head's q and k, as Qwen3 has."""
    k = dims(m)
    w, f32 = weights.serve_dtype(m), "float32"
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
    if qk_norm:
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


def _layer(m, precision, lp, x):
    k = dims(m)
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


def _final(m, precision, head, x):
    k = dims(m)
    # MiniCPM divides the last hidden state by hidden_size / dim_model_base
    x = _rms(x, head["final_norm"], k["eps"]) / (k["d"] / m.get("dim_model_base", k["d"]))
    return reference.unembed(precision, head, x)


def logits(m: dict, params, tokens, precision: str = "float32"):
    x = reference.apply(reference.embed, m, precision, params["head"], tokens)
    x = reference.layers(_layer, m, precision, params["layers"], x)
    return reference.apply(_final, m, precision, params["head"], x)


def reduce(m: dict) -> dict:
    from repro.configs import get_config

    c = get_config(m["arch"]).reduced()
    m = dict(m, vocab_size=c.vocab_size, hidden_size=c.d_model, num_hidden_layers=c.num_layers,
             num_attention_heads=c.num_heads, num_key_value_heads=c.num_kv_heads, head_dim=c.head_dim,
             intermediate_size=c.d_ff)
    if "scale_depth" in m:  # the program has no muP scales: keep them at 1
        m["scale_depth"] = c.num_layers ** 0.5
        m["dim_model_base"] = c.d_model
    return m
