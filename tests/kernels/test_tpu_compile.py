"""Compile the main-path programs for a described TPU v5e chip, without one.

The TPU compiler refuses what interpret mode accepts (unaligned blocks,
reshapes Mosaic cannot lay out, loads from HBM refs), so each kernel is
compiled at qwen3-1.7b widths and each served model's one-token decode step
at its published widths. Nothing runs; only the compiler is exercised.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.runtime import named_step
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.paged_attention.kernel import paged_attention
from repro.kernels.streammm.kernel import stream_matmul, stream_matmul_int8
from repro.models.model import build_model

# qwen3-1.7b: GQA 16/8, head_dim 128, d_model 2048, d_ff 6144
H, HKV, D, D_MODEL, D_FF = 16, 8, 128, 2048, 6144
SEQ, TOKENS, PAGE_TOKENS, PAGES = 512, 2048, 16, 32


@pytest.fixture(scope="module")
def topo(tmp_path_factory):
    from jax.experimental import topologies

    # libtpu logs to /tmp/tpu_logs unless told otherwise ("disabled" only
    # moves the files to /tmp); keep them under pytest's temporary directory
    os.environ.setdefault("TPU_LOG_DIR", str(tmp_path_factory.mktemp("tpu_logs")))
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described-device compile cannot be read back from a persistent
    # cache without the chip; keep it out of any cache that is configured
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


KERNELS = {
    "stream_matmul": (
        stream_matmul,
        [((TOKENS, D_MODEL), jnp.bfloat16), ((D_MODEL, D_FF), jnp.bfloat16)],
    ),
    "stream_matmul_int8": (
        stream_matmul_int8,
        [
            ((TOKENS, D_MODEL), jnp.bfloat16),
            ((D_MODEL, D_FF), jnp.int8),
            ((D_MODEL // 512, D_FF), jnp.float32),
        ],
    ),
    "flash_attention_gqa": (
        flash_attention,
        [
            ((1, SEQ, H, D), jnp.bfloat16),
            ((1, SEQ, HKV, D), jnp.bfloat16),
            ((1, SEQ, HKV, D), jnp.bfloat16),
        ],
    ),
    # minicpm-2b's MHA (g = 1) takes the same kernel with one head per group
    "flash_attention_mha": (
        flash_attention,
        [
            ((1, SEQ, 36, 64), jnp.bfloat16),
            ((1, SEQ, 36, 64), jnp.bfloat16),
            ((1, SEQ, 36, 64), jnp.bfloat16),
        ],
    ),
    "paged_attention": (
        paged_attention,
        [
            ((4, H, D), jnp.bfloat16),
            ((PAGES, PAGE_TOKENS, HKV, D), jnp.bfloat16),
            ((PAGES, PAGE_TOKENS, HKV, D), jnp.bfloat16),
            ((4, PAGES // 4), jnp.int32),
            ((4,), jnp.int32),
        ],
    ),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = KERNELS[name]
    compiled = _compile(fn, shapes, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "mamba2-1.3b", "minicpm-2b"])
def test_full_width_decode_step_compiles_for_v5e(arch, one_chip):
    # the step LiveModelTask jits: forward on one token, all weights resident
    fns = build_model(get_config(arch))
    params = jax.eval_shape(fns.init, jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one_chip), params
    )
    tok = jax.ShapeDtypeStruct((1, 1), jnp.int32, sharding=one_chip)
    compiled = named_step(fns).lower(params, tok).compile()
    mem = compiled.memory_analysis()
    weights = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(params))
    assert mem.argument_size_in_bytes >= weights
    # weights plus the step's own buffers fit one 16 GB chip
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
