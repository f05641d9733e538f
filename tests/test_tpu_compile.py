"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached, at TinyLlama's published widths (B=8 lanes, 32 query / 4
KV heads, head_dim 64, 2048 cache slots, 16-slot pages), and the paged
decode kernel and the grouped expert kernel at the benchmark cells'
widths.

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
from repro.kernels import ops as kops

B, H, KV, D, S, PS = 8, 32, 4, 64, 2048, 16


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


def _paged_args(one_chip, dtype, quantized, b=B, h=H, kvh=KV, d=D, w=S // PS):
    st = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    kv_dt = jnp.int8 if quantized else dtype
    pages = 1 + b * w
    args = [st((b, h, d), dtype), st((pages, kvh, PS, d), kv_dt),
            st((pages, kvh, PS, d), kv_dt), st((b, w), jnp.int32),
            st((b,), jnp.int32)]
    if quantized:
        args += [st((pages, kvh, PS), jnp.float32)] * 2
    return args


def _paged(one_chip, dtype, quantized, **shape):
    args = _paged_args(one_chip, dtype, quantized, **shape)
    if quantized:
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


# the benchmark cells' decode shapes: lanes, query heads, KV heads,
# head_dim and page-table width (cache_len / 16)
CELL_SHAPES = {"qwen3-8b-pp3": dict(b=24, h=32, kvh=8, d=128, w=160),
               "qwen3-0.6b": dict(b=8, h=16, kvh=8, d=128, w=256),
               "qwen3-235b-a22b-ep16": dict(b=64, h=64, kvh=4, d=128,
                                            w=160)}


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
def test_paged_decode_compiles_at_cell_widths(one_chip, cell, quantized):
    dtype = jnp.float32 if quantized else jnp.bfloat16
    text = _paged(one_chip, dtype, quantized, **CELL_SHAPES[cell])
    assert "tpu_custom_call" in text


def test_paged_decode_custom_call_keeps_its_name(one_chip):
    """The benchmark finds the kernel in a device trace by the name of its
    HLO instruction, ``%decode_attention_paged.<n>``, which the jitted
    wrapper in ``kernels/ops.py`` gives it."""
    args = _paged_args(one_chip, jnp.bfloat16, False,
                       **CELL_SHAPES["qwen3-8b-pp3"])
    text = kops.decode_attention_paged.lower(
        *args, interpret=False).compile().as_text()
    names = [line.split("=")[0].strip() for line in text.splitlines()
             if "tpu_custom_call" in line and "=" in line]
    assert names and all(n.startswith("%decode_attention_paged")
                         for n in names), names


@pytest.mark.parametrize("tokens,tile", [(64, 64), (2048, 256)],
                         ids=["decode", "prefill"])
def test_grouped_expert_ffn_compiles_at_cell_widths(one_chip, tokens, tile):
    """The held experts of the MoE cell (12 layers of 8 experts, d 4096,
    width 1536, top 8) for a decode batch of 64 lanes and a 2048-token
    prompt, with the layout the serving layer gives them; the kernel
    keeps the name
    ``%grouped_expert_ffn.<n>`` that the benchmark reads from traces."""
    layers, e, d, f = 12, 8, 4096, 1536
    n = ((tokens * 8) // tile + e + 1) * tile
    st = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    args = [st((n, d), jnp.bfloat16), st((layers, e, d, f), jnp.bfloat16),
            st((layers, e, d, f), jnp.bfloat16),
            st((layers, e, f, d), jnp.bfloat16),
            st((e,), jnp.int32), st((e,), jnp.int32), st((), jnp.int32)]
    text = kops.grouped_expert_ffn.lower(
        *args, tile=tile, chunks=-(-tokens // tile),
        interpret=False).compile().as_text()
    names = [line.split("=")[0].strip() for line in text.splitlines()
             if "tpu_custom_call" in line and "=" in line]
    assert names and all(n.split()[-1].startswith("%grouped_expert_ffn")
                         for n in names), names


def test_flash_prefill_compiles_for_v5e(one_chip):
    st = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                            sharding=one_chip)
    text = _compile_text(
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True),
        st((1, H, 512, D)), st((1, KV, 512, D)), st((1, KV, 512, D)))
    assert "tpu_custom_call" in text
