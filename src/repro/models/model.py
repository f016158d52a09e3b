"""Model assembly: init / forward / prefill / decode_step / loss per family.

All families share the same outer contract so the launchers, the live MSched
runtime, the workload generators and the tests are arch-agnostic:

  init(rng)                         -> params
  forward(params, batch)            -> logits (B, S, V)       [train shapes]
  loss(params, batch)               -> (scalar, aux)
  prefill(params, batch)            -> (last_logits, cache)
  decode_step(params, cache, batch) -> (logits, cache)        [one token]
  init_cache(batch, max_seq)        -> cache pytree

Layer stacks run under ``jax.lax.scan`` (+ optional remat) so that the HLO is
layer-count-independent, which keeps compiles of deep models short.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import common, layers, rglru, ssm
from repro.sharding.act import constrain
from repro.models.common import rms_norm
from repro.models.layers import (
    attention_apply,
    attention_decode,
    init_attention,
    init_mlp,
    init_moe,
    init_norm,
    mlp_apply,
    moe_apply,
)

PyTree = Any


@dataclasses.dataclass(frozen=True)
class ModelFns:
    cfg: ModelConfig
    init: Callable
    forward: Callable
    loss: Callable
    prefill: Callable
    decode_step: Callable
    init_cache: Callable


# --------------------------------------------------------------------------
# Vocab / embedding heads
# --------------------------------------------------------------------------


def _init_head(key, cfg: ModelConfig):
    dt = common.dtype_of(cfg)
    k1, k2 = jax.random.split(key)
    p = {"embed": common.embed_init(k1, (cfg.vocab_size, cfg.d_model), dt)}
    if not cfg.tie_embeddings:
        p["lm_head"] = common.dense_init(k2, (cfg.d_model, cfg.vocab_size), dt)
    p["final_norm"] = init_norm(cfg)
    return p


def _logits(p, x, cfg: ModelConfig):
    x = rms_norm(x, p["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        out = jnp.einsum("bsd,vd->bsv", x, p["embed"])
    else:
        out = jnp.einsum("bsd,dv->bsv", x, p["lm_head"])
    return constrain(out, "dp", None, "tp")


def cross_entropy(logits, labels, ignore: int = -1):
    """Token-mean CE; labels == ignore are masked.

    Written to stay vocab-shard-friendly under GSPMD: the label pick uses an
    iota-compare-select reduction (fuses, shards over V with a small
    all-reduce) instead of take_along_axis, whose gather would all-gather the
    (B, S, V) logits across the model axis.
    """
    logits = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1)) + m[..., 0]
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, len(logits.shape) - 1)
    picked = jnp.where(vocab_iota == labels[..., None], logits, 0.0)
    ll = jnp.sum(picked, axis=-1)
    nll = lse - ll
    mask = (labels != ignore).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# --------------------------------------------------------------------------
# Uniform attention transformer (dense / moe / vlm / audio)
# --------------------------------------------------------------------------


def _init_attn_layer(key, cfg: ModelConfig):
    k1, k2 = jax.random.split(key)
    p = {
        "attn_norm": init_norm(cfg),
        "attn": init_attention(k1, cfg),
        "mlp_norm": init_norm(cfg),
    }
    if cfg.moe is not None:
        p["moe"] = init_moe(k2, cfg)
    else:
        p["mlp"] = init_mlp(k2, cfg)
    return p


def _mix_mlp(lp, x, cfg: ModelConfig):
    h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    if cfg.moe is not None:
        out, router_logits = moe_apply(lp["moe"], h, cfg)
        aux = _load_balance_loss(router_logits, cfg)
        return x + out, aux
    return x + mlp_apply(lp["mlp"], h), jnp.float32(0.0)


def _load_balance_loss(router_logits, cfg: ModelConfig):
    m = cfg.moe
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    top1 = jnp.argmax(probs, axis=-1)
    frac = jnp.mean(jax.nn.one_hot(top1, m.num_experts, dtype=jnp.float32), axis=0)
    mean_p = jnp.mean(probs, axis=0)
    return m.num_experts * jnp.sum(frac * mean_p)


def _remat(fn):
    """Full layer remat (save only the scan carry; recompute everything)."""
    return jax.checkpoint(fn, prevent_cse=False)


def _transformer_fns(cfg: ModelConfig) -> ModelFns:
    L = cfg.num_layers
    hd = cfg.resolved_head_dim()

    def init(rng):
        kh, kl = jax.random.split(rng)
        lkeys = jax.random.split(kl, L)
        return {
            "head": _init_head(kh, cfg),
            "layers": jax.vmap(lambda k: _init_attn_layer(k, cfg))(lkeys),
        }

    def _embed_inputs(params, batch):
        """Returns (x, positions, positions3)."""
        if cfg.family == "audio":
            # frontend stub: precomputed frame embeddings (per assignment)
            x = batch["frames"].astype(common.dtype_of(cfg))
            if "frame_mask" in batch:
                mask_emb = params["head"]["embed"][0]  # id 0 = mask embedding
                x = jnp.where(batch["frame_mask"][..., None], mask_emb, x)
            b, s = x.shape[:2]
            positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
            return x, positions, None
        tokens = batch["tokens"]
        x = params["head"]["embed"][tokens]
        b = tokens.shape[0]
        if cfg.family == "vlm":
            # frontend stub: precomputed patch embeddings (per assignment)
            ve = batch["vision_embeds"].astype(x.dtype)
            x = jnp.concatenate([ve, x], axis=1)
            positions3 = batch["positions3"]  # (3, B, S_total)
            positions = positions3[0]
            return x, positions, positions3
        s = tokens.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        return x, positions, None

    def _run_layers(params, x, positions, positions3, collect_kv: bool):
        # sequence-parallel TP: the residual stream lives seq-sharded over the
        # model axis between blocks; GSPMD turns the per-block collective pair
        # from (all-reduce fwd + all-reduce bwd) into (all-gather + reduce-
        # scatter), halving collective volume (see EXPERIMENTS.md §Perf).
        moe = cfg.moe is not None
        seq_spec = ("dp", "tp", None)
        x = constrain(x, *seq_spec)

        def layer_fn(carry, lp):
            # bare bf16 carry for dense models: a tuple carry makes XLA save
            # an extra f32 copy of the residual stream per layer (measured
            # +14 GB/device at train_4k scale)
            x, aux = carry if moe else (carry, None)
            x = constrain(x, *seq_spec)
            h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
            attn_out, kv = attention_apply(
                lp["attn"], h, cfg, positions=positions, positions3=positions3
            )
            x = x + attn_out
            x, aux_l = _mix_mlp(lp, x, cfg)
            ys = kv if collect_kv else None
            return ((x, aux + aux_l) if moe else x), ys

        f = _remat(layer_fn) if cfg.remat else layer_fn
        init = (x, jnp.float32(0.0)) if moe else x
        out, kvs = jax.lax.scan(f, init, params["layers"])
        x, aux = out if moe else (out, jnp.float32(0.0))
        return x, aux / L, kvs

    def forward(params, batch):
        x, positions, positions3 = _embed_inputs(params, batch)
        x, _, _ = _run_layers(params, x, positions, positions3, collect_kv=False)
        return _logits(params["head"], x, cfg)

    def loss(params, batch):
        x, positions, positions3 = _embed_inputs(params, batch)
        x, aux, _ = _run_layers(params, x, positions, positions3, collect_kv=False)
        if cfg.family == "vlm":
            # only text positions carry labels; vision prefix is unsupervised
            s_vis = batch["vision_embeds"].shape[1]
            x = x[:, s_vis:, :]
        logits = _logits(params["head"], x, cfg)
        labels = batch["labels"]
        if cfg.family == "audio" and "frame_mask" in batch:
            labels = jnp.where(batch["frame_mask"], labels, -1)
        ce = cross_entropy(logits, labels)
        total = ce + 0.01 * aux
        return total, {"ce": ce, "aux": aux}

    def init_cache(batch_size: int, max_seq: int):
        dt = common.dtype_of(cfg)
        shape = (L, batch_size, max_seq, cfg.num_kv_heads, hd)
        return {
            "k": jnp.zeros(shape, dt),
            "v": jnp.zeros(shape, dt),
            "index": jnp.zeros((), jnp.int32),
        }

    def prefill(params, batch, max_seq: Optional[int] = None):
        x, positions, positions3 = _embed_inputs(params, batch)
        x, _, kvs = _run_layers(params, x, positions, positions3, collect_kv=True)
        logits = _logits(params["head"], x[:, -1:, :], cfg)
        k, v = kvs
        s = x.shape[1]
        if max_seq is not None and max_seq > s:
            pad = [(0, 0), (0, 0), (0, max_seq - s), (0, 0), (0, 0)]
            k, v = jnp.pad(k, pad), jnp.pad(v, pad)
        cache = {"k": k, "v": v, "index": jnp.asarray(s, jnp.int32)}
        return logits, cache

    def decode_step(params, cache, batch):
        tokens = batch["tokens"]  # (B, 1)
        x = params["head"]["embed"][tokens]
        b = tokens.shape[0]
        index = cache["index"]
        positions = jnp.broadcast_to(index[None, None], (b, 1)).astype(jnp.int32)
        positions3 = None
        if cfg.family == "vlm":
            positions3 = jnp.broadcast_to(index[None, None, None], (3, b, 1)).astype(
                jnp.int32
            )

        def layer_fn(x, inp):
            lp, kc, vc = inp
            x = constrain(x, "dp", None, None)
            h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
            attn_out, (kc, vc) = attention_decode(
                lp["attn"],
                h,
                cfg,
                k_cache=kc,
                v_cache=vc,
                index=index,
                positions=positions,
                positions3=positions3,
            )
            x = x + attn_out
            x, _ = _mix_mlp(lp, x, cfg)
            return x, (kc, vc)

        x, (ks, vs) = jax.lax.scan(
            layer_fn, x, (params["layers"], cache["k"], cache["v"])
        )
        logits = _logits(params["head"], x, cfg)
        return logits, {"k": ks, "v": vs, "index": index + 1}

    return ModelFns(cfg, init, forward, loss, prefill, decode_step, init_cache)


# --------------------------------------------------------------------------
# SSM (mamba2)
# --------------------------------------------------------------------------


def _ssm_fns(cfg: ModelConfig) -> ModelFns:
    L = cfg.num_layers

    def _init_layer(key, _cfg=cfg):
        return {"norm": init_norm(_cfg), "mixer": ssm.init_ssm(key, _cfg)}

    def init(rng):
        kh, kl = jax.random.split(rng)
        lkeys = jax.random.split(kl, L)
        return {
            "head": _init_head(kh, cfg),
            "layers": jax.vmap(_init_layer)(lkeys),
        }

    def _run(params, x):
        x = constrain(x, "dp", None, None)

        def layer_fn(x, lp):
            x = constrain(x, "dp", None, None)
            h = rms_norm(x, lp["norm"], cfg.norm_eps)
            x = x + ssm.ssm_apply(lp["mixer"], h, cfg)
            return x, None

        f = _remat(layer_fn) if cfg.remat else layer_fn
        x, _ = jax.lax.scan(f, x, params["layers"])
        return x

    def forward(params, batch):
        x = params["head"]["embed"][batch["tokens"]]
        x = _run(params, x)
        return _logits(params["head"], x, cfg)

    def loss(params, batch):
        logits = forward(params, batch)
        ce = cross_entropy(logits, batch["labels"])
        return ce, {"ce": ce, "aux": jnp.float32(0.0)}

    def init_cache(batch_size: int, max_seq: int):
        dt = common.dtype_of(cfg)
        one = ssm.init_ssm_cache(cfg, batch_size, dt)
        return {
            "state": jnp.zeros((L,) + one["state"].shape, one["state"].dtype),
            "conv": jnp.zeros((L,) + one["conv"].shape, one["conv"].dtype),
            "index": jnp.zeros((), jnp.int32),
        }

    def prefill(params, batch, max_seq: Optional[int] = None):
        # Constant-size decode state: max_seq is irrelevant (O(1) cache).
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = params["head"]["embed"][tokens]

        def layer_fn(x, lp):
            h = rms_norm(x, lp["norm"], cfg.norm_eps)
            out, st = ssm.ssm_apply_with_state(lp["mixer"], h, cfg)
            return x + out, st

        x, states = jax.lax.scan(layer_fn, x, params["layers"])
        logits = _logits(params["head"], x[:, -1:, :], cfg)
        cache = {
            "state": states["state"],
            "conv": states["conv"],
            "index": jnp.asarray(s, jnp.int32),
        }
        return logits, cache

    def decode_step(params, cache, batch):
        x = params["head"]["embed"][batch["tokens"]]

        def layer_fn(x, inp):
            lp, st, cv = inp
            x = constrain(x, "dp", None, None)
            h = rms_norm(x, lp["norm"], cfg.norm_eps)
            out, new = ssm.ssm_decode(lp["mixer"], h, {"state": st, "conv": cv}, cfg)
            return x + out, (new["state"], new["conv"])

        x, (sts, cvs) = jax.lax.scan(
            layer_fn, x, (params["layers"], cache["state"], cache["conv"])
        )
        logits = _logits(params["head"], x, cfg)
        return logits, {"state": sts, "conv": cvs, "index": cache["index"] + 1}

    return ModelFns(cfg, init, forward, loss, prefill, decode_step, init_cache)


# --------------------------------------------------------------------------
# Hybrid (recurrentgemma): (rec, rec, attn) pattern blocks
# --------------------------------------------------------------------------


def _hybrid_counts(cfg: ModelConfig):
    kinds = cfg.layer_kinds()
    n_rec = sum(1 for k in kinds if k == "rec")
    n_attn = sum(1 for k in kinds if k == "attn")
    pat = cfg.rglru.pattern
    n_groups = cfg.num_layers // len(pat)
    n_rem = cfg.num_layers - n_groups * len(pat)
    return kinds, n_rec, n_attn, n_groups, n_rem


def _hybrid_fns(cfg: ModelConfig) -> ModelFns:
    kinds, n_rec, n_attn, n_groups, n_rem = _hybrid_counts(cfg)
    assert cfg.rglru.pattern == ("rec", "rec", "attn")
    # trailing remainder layers are recurrent blocks (pattern truncation)
    window = cfg.rglru.window
    hd = cfg.resolved_head_dim()

    def _init_rec(key, _cfg=cfg):
        k1, k2 = jax.random.split(key)
        return {
            "norm": init_norm(_cfg),
            "rec": rglru.init_rec_block(k1, _cfg),
            "mlp_norm": init_norm(_cfg),
            "mlp": init_mlp(k2, _cfg),
        }

    def _init_attn(key, _cfg=cfg):
        k1, k2 = jax.random.split(key)
        return {
            "norm": init_norm(_cfg),
            "attn": init_attention(k1, _cfg),
            "mlp_norm": init_norm(_cfg),
            "mlp": init_mlp(k2, _cfg),
        }

    def init(rng):
        kh, kr, ka = jax.random.split(rng, 3)
        return {
            "head": _init_head(kh, cfg),
            "rec_layers": jax.vmap(_init_rec)(jax.random.split(kr, n_rec)),
            "attn_layers": jax.vmap(_init_attn)(jax.random.split(ka, n_attn)),
        }

    def _split_groups(params):
        """rec stack -> (groups of 2, remainder); attn stack used per group."""
        rec = params["rec_layers"]
        grouped = jax.tree.map(
            lambda a: a[: 2 * n_groups].reshape((n_groups, 2) + a.shape[1:]), rec
        )
        rem = jax.tree.map(lambda a: a[2 * n_groups :], rec)
        return grouped, rem

    def _rec_apply(lp, x, h0=None, conv0=None, decode=False, positions=None):
        h = rms_norm(x, lp["norm"], cfg.norm_eps)
        if decode:
            out, h_new, conv_new = rglru.rec_block_decode(
                lp["rec"], h, h0, cfg, conv0
            )
        else:
            out, h_new = rglru.rec_block_apply(lp["rec"], h, cfg, h0)
            conv_new = None
        x = x + out
        m = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        x = x + mlp_apply(lp["mlp"], m)
        return x, h_new, conv_new

    def _attn_apply_full(lp, x, positions, collect_kv=False):
        h = rms_norm(x, lp["norm"], cfg.norm_eps)
        out, kv = attention_apply(
            lp["attn"], h, cfg, positions=positions, window=window
        )
        x = x + out
        m = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        x = x + mlp_apply(lp["mlp"], m)
        return x, kv

    def _run_full(params, x, positions, collect: bool):
        grouped, rem = _split_groups(params)
        x = constrain(x, "dp", None, None)

        def group_fn(x, inp):
            x = constrain(x, "dp", None, None)
            recs, attn = inp
            r0 = jax.tree.map(lambda a: a[0], recs)
            r1 = jax.tree.map(lambda a: a[1], recs)
            x, h0, _ = _rec_apply(r0, x)
            x, h1, _ = _rec_apply(r1, x)
            x, kv = _attn_apply_full(attn, x, positions)
            ys = (jnp.stack([h0, h1]), kv) if collect else None
            return x, ys

        f = _remat(group_fn) if cfg.remat else group_fn
        x, ys = jax.lax.scan(f, x, (grouped, params["attn_layers"]))
        rem_states = []
        for i in range(n_rem):
            lp = jax.tree.map(lambda a: a[i], rem)
            x, h, _ = _rec_apply(lp, x)
            rem_states.append(h)
        return x, ys, rem_states

    def forward(params, batch):
        tokens = batch["tokens"]
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        x = params["head"]["embed"][tokens]
        x, _, _ = _run_full(params, x, positions, collect=False)
        return _logits(params["head"], x, cfg)

    def loss(params, batch):
        logits = forward(params, batch)
        ce = cross_entropy(logits, batch["labels"])
        return ce, {"ce": ce, "aux": jnp.float32(0.0)}

    def init_cache(batch_size: int, max_seq: int):
        dt = common.dtype_of(cfg)
        w = min(window, max_seq)
        cw = cfg.rglru.conv_width
        return {
            "h": jnp.zeros((n_rec, batch_size, cfg.d_model), jnp.float32),
            "conv": jnp.zeros((n_rec, batch_size, cw - 1, cfg.d_model), dt),
            "k": jnp.zeros((n_attn, batch_size, w, cfg.num_kv_heads, hd), dt),
            "v": jnp.zeros((n_attn, batch_size, w, cfg.num_kv_heads, hd), dt),
            "index": jnp.zeros((), jnp.int32),
        }

    def prefill(params, batch, max_seq: Optional[int] = None):
        # Bounded decode state: ring buffer of `window` slots (max_seq only
        # matters when the prefill is shorter than the window).
        tokens = batch["tokens"]
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        x = params["head"]["embed"][tokens]
        grouped, rem = _split_groups(params)
        w = min(window, s)

        def seed_ring(arr):  # (B, S, Hkv, hd) -> ring-ordered last w slots
            tail = arr[:, -w:]
            return jnp.roll(tail, (s - w) % w, axis=1)

        def group_fn(x, inp):
            recs, attn = inp
            r0 = jax.tree.map(lambda a: a[0], recs)
            r1 = jax.tree.map(lambda a: a[1], recs)
            # also collect conv tails for decode continuation
            h_in0 = rms_norm(x, r0["norm"], cfg.norm_eps)
            conv0 = rglru.conv_tail(r0["rec"], h_in0)
            x, h0, _ = _rec_apply(r0, x)
            h_in1 = rms_norm(x, r1["norm"], cfg.norm_eps)
            conv1 = rglru.conv_tail(r1["rec"], h_in1)
            x, h1, _ = _rec_apply(r1, x)
            x, (k, v) = _attn_apply_full(attn, x, positions)
            ys = (
                jnp.stack([h0, h1]),
                jnp.stack([conv0, conv1]),
                seed_ring(k),
                seed_ring(v),
            )
            return x, ys

        x, (hs, convs, ks, vs) = jax.lax.scan(
            group_fn, x, (grouped, params["attn_layers"])
        )
        rem_h, rem_conv = [], []
        for i in range(n_rem):
            lp = jax.tree.map(lambda a: a[i], rem)
            h_in = rms_norm(x, lp["norm"], cfg.norm_eps)
            rem_conv.append(rglru.conv_tail(lp["rec"], h_in))
            x, h, _ = _rec_apply(lp, x)
            rem_h.append(h)
        h_parts = [hs.reshape((-1,) + hs.shape[2:])]
        conv_parts = [convs.reshape((-1,) + convs.shape[2:])]
        if n_rem:
            h_parts.append(jnp.stack(rem_h))
            conv_parts.append(jnp.stack(rem_conv))
        h_all = jnp.concatenate(h_parts)
        conv_all = jnp.concatenate(conv_parts)
        logits = _logits(params["head"], x[:, -1:, :], cfg)
        cache = {
            "h": h_all,
            "conv": conv_all,
            "k": ks,
            "v": vs,
            "index": jnp.asarray(s, jnp.int32),
        }
        return logits, cache

    def decode_step(params, cache, batch):
        x = params["head"]["embed"][batch["tokens"]]
        b = x.shape[0]
        index = cache["index"]
        positions = jnp.broadcast_to(index[None, None], (b, 1)).astype(jnp.int32)
        grouped, rem = _split_groups(params)
        h_g = cache["h"][: 2 * n_groups].reshape(
            (n_groups, 2) + cache["h"].shape[1:]
        )
        conv_g = cache["conv"][: 2 * n_groups].reshape(
            (n_groups, 2) + cache["conv"].shape[1:]
        )

        def group_fn(x, inp):
            recs, attn, hs, cvs, kc, vc = inp
            r0 = jax.tree.map(lambda a: a[0], recs)
            r1 = jax.tree.map(lambda a: a[1], recs)
            x, h0, c0 = _rec_apply(r0, x, hs[0], cvs[0], decode=True)
            x, h1, c1 = _rec_apply(r1, x, hs[1], cvs[1], decode=True)
            h = rms_norm(x, attn["norm"], cfg.norm_eps)
            out, (kc, vc) = attention_decode(
                attn["attn"],
                h,
                cfg,
                k_cache=kc,
                v_cache=vc,
                index=index,
                positions=positions,
                window=window,
                ring=True,
            )
            x = x + out
            m = rms_norm(x, attn["mlp_norm"], cfg.norm_eps)
            x = x + mlp_apply(attn["mlp"], m)
            return x, (jnp.stack([h0, h1]), jnp.stack([c0, c1]), kc, vc)

        x, (hs, cvs, ks, vs) = jax.lax.scan(
            group_fn,
            x,
            (grouped, params["attn_layers"], h_g, conv_g, cache["k"], cache["v"]),
        )
        new_h = [hs.reshape((-1,) + hs.shape[2:])]
        new_conv = [cvs.reshape((-1,) + cvs.shape[2:])]
        for i in range(n_rem):
            lp = jax.tree.map(lambda a: a[i], rem)
            x, h, c = _rec_apply(
                lp, x, cache["h"][2 * n_groups + i], cache["conv"][2 * n_groups + i],
                decode=True,
            )
            new_h.append(h[None])
            new_conv.append(c[None])
        logits = _logits(params["head"], x, cfg)
        cache = {
            "h": jnp.concatenate(new_h),
            "conv": jnp.concatenate(new_conv),
            "k": ks,
            "v": vs,
            "index": index + 1,
        }
        return logits, cache

    return ModelFns(cfg, init, forward, loss, prefill, decode_step, init_cache)


# --------------------------------------------------------------------------
# Dispatch
# --------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def build_model(cfg: ModelConfig) -> ModelFns:
    if cfg.family == "ssm":
        return _ssm_fns(cfg)
    if cfg.family == "hybrid":
        return _hybrid_fns(cfg)
    return _transformer_fns(cfg)
