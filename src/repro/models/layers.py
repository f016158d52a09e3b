"""Attention / MLP / MoE layers: init + apply (pure functions over pytrees).

Layer params are plain dicts so they can be stacked with a leading layer axis
and driven by ``jax.lax.scan`` (keeps HLO small and compiles short).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig
from repro.models import common
from repro.models.common import apply_mrope, apply_rope, attend, dense_init, rms_norm
from repro.sharding.act import axis_size, constrain

# --------------------------------------------------------------------------
# Attention layer
# --------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig):
    d = cfg.d_model
    hd = cfg.resolved_head_dim()
    h, hkv = cfg.num_heads, cfg.num_kv_heads
    dt = common.dtype_of(cfg)
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, h * hd), dt),
        "wk": dense_init(ks[1], (d, hkv * hd), dt),
        "wv": dense_init(ks[2], (d, hkv * hd), dt),
        "wo": dense_init(ks[3], (h * hd, d), dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dt)
        p["bk"] = jnp.zeros((hkv * hd,), dt)
        p["bv"] = jnp.zeros((hkv * hd,), dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), jnp.float32)
        p["k_norm"] = jnp.zeros((hd,), jnp.float32)
    return p


def _project_qkv(p, x, cfg: ModelConfig, positions, positions3):
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim()
    q = jnp.einsum("bsd,de->bse", x, p["wq"])
    k = jnp.einsum("bsd,de->bse", x, p["wk"])
    v = jnp.einsum("bsd,de->bse", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, cfg.num_heads, hd)
    k = k.reshape(b, s, cfg.num_kv_heads, hd)
    v = v.reshape(b, s, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.mrope_sections is not None:
        assert positions3 is not None
        q = apply_mrope(q, positions3, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, positions3, cfg.rope_theta, cfg.mrope_sections)
    elif cfg.causal:  # encoder (hubert) backbone: no rope on bidirectional attn
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attention_apply(
    p,
    x,
    cfg: ModelConfig,
    *,
    positions,
    positions3=None,
    window: Optional[int] = None,
):
    """Full-sequence attention (train / prefill). Returns (out, (k, v))."""
    q, k, v = _project_qkv(p, x, cfg, positions, positions3)
    out = attend(
        q,
        k,
        v,
        q_positions=positions,
        kv_positions=positions,
        causal=cfg.causal,
        window=window,
    )
    b, s, _, _ = out.shape
    out = jnp.einsum("bse,ed->bsd", out.reshape(b, s, -1), p["wo"])
    return out, (k, v)


def attention_decode(
    p,
    x,
    cfg: ModelConfig,
    *,
    k_cache,
    v_cache,
    index,
    positions,
    positions3=None,
    window: Optional[int] = None,
    ring: bool = False,
):
    """One-token decode against a KV cache.

    k_cache/v_cache: (B, Smax, Hkv, Dh); index: scalar int32 (current position)
    positions: (B, 1) current absolute position. With ``ring=True`` the cache
    is a ring buffer of size Smax (sliding-window layers).
    """
    q, k, v = _project_qkv(p, x, cfg, positions, positions3)
    smax = k_cache.shape[1]
    slot = (index % smax) if ring else index
    k_cache = jax.lax.dynamic_update_slice(k_cache, k, (0, slot, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(v_cache, v, (0, slot, 0, 0))
    b = x.shape[0]
    iota = jnp.arange(smax, dtype=jnp.int32)[None, :]
    if ring:
        # absolute position stored in slot j: largest p <= index with p % smax == j
        kv_pos = index - ((index - iota) % smax)
        kv_pos = jnp.where(kv_pos < 0, -1, kv_pos)
    else:
        kv_pos = jnp.where(iota <= index, iota, -1)
    kv_pos = jnp.broadcast_to(kv_pos, (b, smax)).astype(jnp.int32)
    out = attend(
        q,
        k_cache,
        v_cache,
        q_positions=positions,
        kv_positions=kv_pos,
        causal=True,
        window=window,
    )
    out = jnp.einsum("bse,ed->bsd", out.reshape(b, 1, -1), p["wo"])
    return out, (k_cache, v_cache)


# --------------------------------------------------------------------------
# Dense (gated) MLP
# --------------------------------------------------------------------------


def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None):
    d = cfg.d_model
    f = d_ff if d_ff is not None else cfg.d_ff
    dt = common.dtype_of(cfg)
    ks = jax.random.split(key, 3)
    return {
        "w1": dense_init(ks[0], (d, f), dt),
        "w3": dense_init(ks[1], (d, f), dt),
        "w2": dense_init(ks[2], (f, d), dt),
    }


def mlp_apply(p, x):
    h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, p["w1"]))
    h = h * jnp.einsum("bsd,df->bsf", x, p["w3"])
    return jnp.einsum("bsf,fd->bsd", h, p["w2"])


# --------------------------------------------------------------------------
# Mixture of Experts (capacity-based, scatter/gather dispatch)
# --------------------------------------------------------------------------


def init_moe(key, cfg: ModelConfig):
    assert cfg.moe is not None
    m: MoEConfig = cfg.moe
    d = cfg.d_model
    dt = common.dtype_of(cfg)
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, m.num_experts), jnp.float32),
        "w1": dense_init(ks[1], (m.num_experts, d, m.d_ff), dt),
        "w3": dense_init(ks[2], (m.num_experts, d, m.d_ff), dt),
        "w2": dense_init(ks[3], (m.num_experts, m.d_ff, d), dt),
    }
    if m.dense_residual:
        p["dense"] = init_mlp(ks[4], cfg, d_ff=m.dense_d_ff)
    return p


def moe_capacity(m: MoEConfig, n_tokens: int) -> int:
    c = int(math.ceil(m.top_k * n_tokens / m.num_experts * m.capacity_factor))
    return max(c, m.top_k)


def moe_apply(p, x, cfg: ModelConfig):
    """Capacity-based top-k MoE.

    Dispatch/combine use scatter/gather (token sort into expert slots) rather
    than dense one-hot einsums: the (tokens × experts × capacity) einsum would
    dominate compiled FLOPs by >100× and bury the useful compute.
    """
    m = cfg.moe
    b, s, d = x.shape
    n = b * s
    tokens = x.reshape(n, d)
    router_logits = jnp.einsum(
        "nd,de->ne", tokens.astype(jnp.float32), p["router"]
    )
    probs = jax.nn.softmax(router_logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, m.top_k)  # (n, k)
    gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-9)

    cap = moe_capacity(m, n)
    # choice-major flattening: (k*n,) assignments
    flat_e = idx.T.reshape(-1)
    flat_g = gates.T.reshape(-1)
    oh = jax.nn.one_hot(flat_e, m.num_experts, dtype=jnp.int32)  # (kn, E)
    pos_in_e = jnp.cumsum(oh, axis=0) - oh
    posn = jnp.sum(pos_in_e * oh, axis=-1)  # (kn,)
    keep = posn < cap
    slot = flat_e * cap + jnp.where(keep, posn, 0)  # (kn,)

    token_rep = jnp.tile(tokens, (m.top_k, 1))  # (kn, d)
    token_rep = constrain(token_rep, "dp", None)
    buf = jnp.zeros((m.num_experts * cap, d), tokens.dtype)
    buf = buf.at[slot].add(
        jnp.where(keep[:, None], token_rep, 0), mode="drop"
    )
    # expert-shard the dispatch buffer (EP mode only, E % |model| == 0):
    # without this GSPMD replicates it and all-reduces ~2x its global size
    # per layer (94 s collective term on arctic prefill_32k, §Perf It. 4).
    # In per-expert-TP mode (grok: 8 experts on a 16-way axis) the flat
    # constraint mis-shards across expert boundaries and inflates compiled
    # FLOPs 8.8x — leave GSPMD free there.
    tp_n = axis_size("tp")
    ep_mode = tp_n > 0 and m.num_experts % tp_n == 0
    if ep_mode:
        buf = constrain(buf, "tp", None)
    expert_in = buf.reshape(m.num_experts, cap, d)
    if ep_mode:
        expert_in = constrain(expert_in, "tp", None, None)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, p["w1"]))
    h = h * jnp.einsum("ecd,edf->ecf", expert_in, p["w3"])
    expert_out = jnp.einsum("ecf,efd->ecd", h, p["w2"])  # (E, C, d)
    if ep_mode:
        expert_out = constrain(expert_out, "tp", None, None)

    out_rep = expert_out.reshape(m.num_experts * cap, d)[slot]
    out_rep = constrain(out_rep, "dp", None)
    out_rep = jnp.where(keep[:, None], out_rep, 0) * flat_g[:, None].astype(
        out_rep.dtype
    )
    out = jnp.sum(out_rep.reshape(m.top_k, n, d), axis=0)

    if m.dense_residual:
        out = out + mlp_apply(p["dense"], x).reshape(n, d)
    return out.reshape(b, s, d), router_logits


# --------------------------------------------------------------------------
# Norm params
# --------------------------------------------------------------------------


def init_norm(cfg: ModelConfig):
    return jnp.zeros((cfg.d_model,), jnp.float32)
