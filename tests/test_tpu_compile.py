"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached, at TinyLlama's published widths (B=8 lanes, 32 query / 4
KV heads, head_dim 64, 2048 cache slots, 16-slot pages).

Interpret mode (every other kernel test) cannot see the TPU's tiling
rules; the chip's compiler, which is installed here, can.  Each case
lowers and compiles one kernel for the described chip and checks that
the result holds a Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and test workers
import every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_attention as da
from repro.kernels import flash_attention as fa

B, H, KV, D, S, PS = 8, 32, 4, 64, 2048, 16
PAGES = 1 + B * (S // PS)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _compile_text(fn, *structs):
    return jax.jit(fn).lower(*structs).compile().as_text()


def _ring(one_chip, dtype, quantized):
    st = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    kv_dt = jnp.int8 if quantized else dtype
    args = [st((B, H, D), dtype), st((B, KV, S, D), kv_dt),
            st((B, KV, S, D), kv_dt), st((B,), jnp.int32)]
    if quantized:
        args += [st((B, KV, S), jnp.float32)] * 2
        return _compile_text(
            lambda q, k, v, n, ks, vs: da.decode_attention(
                q, k, v, n, k_scale=ks, v_scale=vs), *args)
    return _compile_text(
        lambda q, k, v, n: da.decode_attention(q, k, v, n), *args)


def _paged(one_chip, dtype, quantized):
    st = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    kv_dt = jnp.int8 if quantized else dtype
    args = [st((B, H, D), dtype), st((PAGES, KV, PS, D), kv_dt),
            st((PAGES, KV, PS, D), kv_dt), st((B, S // PS), jnp.int32),
            st((B,), jnp.int32)]
    if quantized:
        args += [st((PAGES, KV, PS), jnp.float32)] * 2
        return _compile_text(
            lambda q, k, v, pt, n, ks, vs: da.decode_attention_paged(
                q, k, v, pt, n, k_scale=ks, v_scale=vs), *args)
    return _compile_text(
        lambda q, k, v, pt, n: da.decode_attention_paged(q, k, v, pt, n),
        *args)


@pytest.mark.parametrize("layout,dtype,quantized", [
    ("ring", jnp.float32, False),
    ("ring", jnp.bfloat16, False),
    ("paged", jnp.bfloat16, False),
    ("ring", jnp.float32, True),
    ("paged", jnp.float32, True),
], ids=["ring-f32", "ring-bf16", "paged-bf16", "ring-int8", "paged-int8"])
def test_decode_attention_compiles_for_v5e(one_chip, layout, dtype,
                                           quantized):
    build = _ring if layout == "ring" else _paged
    assert "tpu_custom_call" in build(one_chip, dtype, quantized)


def test_flash_prefill_compiles_for_v5e(one_chip):
    st = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                            sharding=one_chip)
    text = _compile_text(
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True),
        st((1, H, 512, D)), st((1, KV, 512, D)), st((1, KV, 512, D)))
    assert "tpu_custom_call" in text
