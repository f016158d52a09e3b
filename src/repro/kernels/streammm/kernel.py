"""Weight-streaming matmul kernel — the TPU-native embodiment of MSched's
pipelined migration + early execution (§6.3), one level down the memory
hierarchy.

On the GPU, MSched overlaps D2H eviction with H2D population on dual copy
engines and starts compute as soon as the first pages land. On TPU the same
insight maps to HBM->VMEM: weights live in the "slow" tier (HBM — or host
DRAM via the runtime's proactive scheduler) and are streamed tile-by-tile
into VMEM while the MXU consumes the previous tile. ``pl.pallas_call``'s
grid pipeline performs exactly this double buffering: BlockSpecs declare the
per-step working set (the "predicted pages" of the tile), and the compiler
overlaps the DMA for step i+1 with compute for step i — proactive, not
fault-driven.

Variants:
  * bf16 x bf16 -> f32 accumulate
  * int8 weights x bf16 activations with fused per-tile dequant (the paper's
    llama.cpp int8 workload): streaming quantized weights halves the
    slow-tier bandwidth demand, the §6.3 bottleneck.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mm_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k: int):
    """Grid = (M/bm, N/bn, K/bk); K is the innermost (fastest) dimension so
    the weight tile stream is sequential in K — the first-access order the
    migration pipeline wants."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def stream_matmul(
    x: jax.Array,  # (M, K)
    w: jax.Array,  # (K, N)
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    out_dtype=jnp.bfloat16,
    interpret: bool = False,
) -> jax.Array:
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    n_k = k // bk
    grid = (m // bm, n // bn, n_k)
    return pl.pallas_call(
        functools.partial(_mm_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w)


def _mm_int8_kernel(x_ref, w_ref, scale_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # fused dequant: int8 tile -> f32 with per-(k-block, out-column) scale
    w_tile = w_ref[...].astype(jnp.float32) * scale_ref[...]
    acc_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32),
        w_tile,
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def stream_matmul_int8(
    x: jax.Array,  # (M, K) bf16/f32
    w_q: jax.Array,  # (K, N) int8
    scales: jax.Array,  # (K // block_k, N) f32 — per k-block column scales
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    out_dtype=jnp.bfloat16,
    interpret: bool = False,
) -> jax.Array:
    m, k = x.shape
    k2, n = w_q.shape
    assert k == k2
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    assert scales.shape == (k // bk, n), (scales.shape, (k // bk, n))
    n_k = k // bk
    grid = (m // bm, n // bn, n_k)
    # a (1, bn) block of the 2-D scales is refused by Mosaic (second-minor
    # block dim must be a multiple of 8 or the whole axis); a leading
    # squeezed k-block axis over (n_k, 1, N) leaves a legal (1, bn) tile
    scales3 = scales.reshape(n_k, 1, n)
    return pl.pallas_call(
        functools.partial(_mm_int8_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((None, 1, bn), lambda i, j, kk: (kk, 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w_q, scales3)
