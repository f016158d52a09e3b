"""Tiled online-softmax attention (prefill hot spot).

Grid = (batch*kv_heads, q_blocks); the kernel loops over KV blocks
with running max/denominator so the (Sq, Skv) score matrix never leaves
VMEM-tile granularity. Supports GQA (q heads grouped per kv head), causal
masking, and a sliding window (recurrentgemma's local attention).

BlockSpecs stage q/k/v tiles HBM->VMEM; the Pallas grid pipeline overlaps the
next tile's DMA with the current tile's MXU work — same proactive-staging
principle as the MSched migration pipeline (§6.3), applied at the VMEM tier.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(
    q_ref,  # (1, 1, g*bq, d) — rows ordered (group, position)
    k_ref,  # (1, skv, d)
    v_ref,  # (1, skv, d)
    o_ref,  # (1, 1, g*bq, d)
    *,
    block_q: int,
    block_kv: int,
    causal: bool,
    window: int,
    sm_scale: float,
):
    rows, d = q_ref.shape[2], q_ref.shape[3]
    skv = k_ref.shape[1]
    qi = pl.program_id(1)
    q = q_ref[0, 0].astype(jnp.float32) * sm_scale  # (g*bq, d)

    m = jnp.full((rows, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((rows, 1), jnp.float32)
    acc = jnp.zeros((rows, d), jnp.float32)

    # row r holds query position r % bq of group r // bq; built without a
    # reshape, which Mosaic cannot lower for a (bq, g) -> (bq*g, 1) cast
    q_pos = qi * block_q + jax.lax.rem(
        jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0), block_q
    )

    n_kv = skv // block_kv

    def body(i, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(i * block_kv, block_kv), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(i * block_kv, block_kv), :].astype(jnp.float32)
        s = q @ k.T  # (g*bq, block_kv)
        kv_pos = i * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_kv), 1
        )
        mask = jnp.ones_like(s, dtype=bool)
        if causal:
            mask = q_pos >= kv_pos
        if window > 0:
            mask = jnp.logical_and(mask, (q_pos - kv_pos) < window)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + p @ v
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(0, n_kv, body, (m, l, acc))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Skv, Hkv, D)
    v: jax.Array,  # (B, Skv, Hkv, D)
    *,
    causal: bool = True,
    window: int = 0,  # 0 = unlimited
    block_q: int = 256,
    block_kv: int = 256,
    sm_scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    b, sq, h, d = q.shape
    _, skv, hkv, _ = k.shape
    assert h % hkv == 0
    g = h // hkv
    sm_scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(d)
    bq = min(block_q, sq)
    bkv = min(block_kv, skv)
    assert sq % bq == 0 and skv % bkv == 0

    # layout: fold q heads into (B*Hkv) batch; each q block's g heads are
    # stacked as one (g*bq, d) tile so the kernel needs no in-VMEM reshape
    nq = sq // bq
    qg = (
        q.reshape(b, nq, bq, hkv, g, d)
        .transpose(0, 3, 1, 4, 2, 5)
        .reshape(b * hkv, nq, g * bq, d)
    )
    kg = k.transpose(0, 2, 1, 3).reshape(b * hkv, skv, d)
    vg = v.transpose(0, 2, 1, 3).reshape(b * hkv, skv, d)

    grid = (b * hkv, nq)
    out = pl.pallas_call(
        functools.partial(
            _fa_kernel,
            block_q=bq,
            block_kv=bkv,
            causal=causal,
            window=window,
            sm_scale=sm_scale,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g * bq, d), lambda bh, qi: (bh, qi, 0, 0)),
            pl.BlockSpec((1, skv, d), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((1, skv, d), lambda bh, qi: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g * bq, d), lambda bh, qi: (bh, qi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hkv, nq, g * bq, d), q.dtype),
        interpret=interpret,
    )(qg, kg, vg)
    return (
        out.reshape(b, hkv, nq, g, bq, d)
        .transpose(0, 2, 4, 1, 3, 5)
        .reshape(b, sq, h, d)
    )
