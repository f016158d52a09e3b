"""Paged-KV decode attention: MSched page-granular memory applied to the KV
cache.

The KV cache lives in a page pool ``(n_pages, page_tokens, Hkv, D)``; each
sequence owns a page table ``(B, max_pages)`` of pool indices — exactly the
page abstraction MSched schedules between HBM and host DRAM, so a sequence's
resident working set is its page list and the runtime can predict it (T2:
linear in the current sequence length, §5.1's KV-cache example).

Grid = (B, Hkv). The page loop walks only the pages < current length,
accumulating online softmax. The pool stays in HBM (``pl.ANY``); each page
is DMA'd into a VMEM tile by dynamic index (scalar prefetch of the page
table).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _pa_kernel(
    ptab_ref,  # scalar-prefetch: (B, max_pages) int32
    lens_ref,  # scalar-prefetch: (B,) int32
    q_ref,  # (1, 1, g, d)
    pool_k_ref,  # (n_pages, pt, d)   [whole pool, ANY memory]
    pool_v_ref,
    o_ref,  # (1, 1, g, d)
    k_buf,  # VMEM (pt, d) landing tile for one K page
    v_buf,
    sem,  # DMA semaphores (2,)
    *,
    page_tokens: int,
    max_pages: int,
    sm_scale: float,
):
    b = pl.program_id(0)
    g, d = q_ref.shape[2], q_ref.shape[3]
    q = q_ref[0, 0].astype(jnp.float32) * sm_scale  # (g, d)
    seq_len = lens_ref[b]
    n_pages = (seq_len + page_tokens - 1) // page_tokens

    def body(p, carry):
        m, l, acc = carry
        page_id = ptab_ref[b, p]
        # the pool stays in HBM; each page is DMA'd into VMEM before use
        k_copy = pltpu.make_async_copy(pool_k_ref.at[page_id], k_buf, sem.at[0])
        v_copy = pltpu.make_async_copy(pool_v_ref.at[page_id], v_buf, sem.at[1])
        k_copy.start()
        v_copy.start()
        k_copy.wait()
        v_copy.wait()
        k = k_buf[...].astype(jnp.float32)  # (pt, d)
        v = v_buf[...].astype(jnp.float32)
        s = q @ k.T  # (g, pt)
        pos = p * page_tokens + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_tokens), 1
        )
        s = jnp.where(pos < seq_len, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        pr = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(pr, axis=-1, keepdims=True)
        acc = acc * alpha + pr @ v
        return m_new, l, acc

    m = jnp.full((g, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((g, 1), jnp.float32)
    acc = jnp.zeros((g, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_pages, body, (m, l, acc))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def paged_attention(
    q: jax.Array,  # (B, H, D) — one decode token per sequence
    pool_k: jax.Array,  # (n_pages, page_tokens, Hkv, D)
    pool_v: jax.Array,
    page_table: jax.Array,  # (B, max_pages) int32
    lengths: jax.Array,  # (B,) int32 current sequence lengths
    *,
    sm_scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    b, h, d = q.shape
    n_pages_pool, pt, hkv, _ = pool_k.shape
    assert h % hkv == 0
    g = h // hkv
    max_pages = page_table.shape[1]
    sm_scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(d)

    qg = q.reshape(b, 1, hkv, g, d).transpose(0, 2, 1, 3, 4).reshape(b, hkv, 1, g, d)

    outs = []
    # one pallas call per kv head keeps the pool BlockSpec simple; heads are
    # data-parallel (the launcher vmaps/shards them in production)
    for kvh in range(hkv):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, 1, g, d), lambda i, *_: (i, 0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, 1, g, d), lambda i, *_: (i, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((pt, d), pool_k.dtype),
                pltpu.VMEM((pt, d), pool_v.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        )
        out = pl.pallas_call(
            functools.partial(
                _pa_kernel,
                page_tokens=pt,
                max_pages=max_pages,
                sm_scale=sm_scale,
            ),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, 1, g, d), q.dtype),
            interpret=interpret,
        )(page_table, lengths, qg[:, kvh], pool_k[:, :, kvh], pool_v[:, :, kvh])
        outs.append(out)
    out = jnp.stack(outs, axis=1)  # (b, hkv, 1, g, d)
    return out.reshape(b, hkv * g, d)
