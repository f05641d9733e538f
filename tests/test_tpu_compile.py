"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached, at TinyLlama's published widths (B=8 lanes, 32 query / 4
KV heads, head_dim 64, 2048 cache slots, 16-slot pages), and the paged
decode kernel (on one layer's pools and on stacked ones) and the grouped
expert kernel at the benchmark cells' widths; and the scheduler's whole
paged decode step, to see that it updates the pools in place.

Interpret mode (every other kernel test) cannot see the TPU's tiling
rules; the chip's compiler, which is installed here, can.  Each kernel
case lowers and compiles one kernel for the described chip and checks
that the result holds a Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and test workers
import every test file.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import models
from repro.configs.base import get_config, reduced
from repro.kernels import decode_attention as da
from repro.kernels import flash_attention as fa
from repro.kernels import ops as kops
from repro.launch.hlo_analysis import whole_buffer_ops
from repro.runtime.scheduler import ContinuousBatchingScheduler

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


def _paged_args(one_chip, dtype, quantized, b=B, h=H, kvh=KV, d=D, w=S // PS,
                layers=None):
    """The paged kernel's arguments; with ``layers`` the pools of that
    many layers stacked, and a layer index last."""
    st = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    kv_dt = jnp.int8 if quantized else dtype
    lead = () if layers is None else (layers,)
    pages = 1 + b * w
    args = [st((b, h, d), dtype), st((*lead, pages, kvh, PS, d), kv_dt),
            st((*lead, pages, kvh, PS, d), kv_dt), st((b, w), jnp.int32),
            st((b,), jnp.int32)]
    if quantized:
        args += [st((*lead, pages, kvh, PS), jnp.float32)] * 2
    if layers is not None:
        args.append(st((), jnp.int32))
    return args


def _paged_fn(quantized, stacked):
    def fn(q, k, v, pt, n, *rest):
        ks, vs = rest[:2] if quantized else (None, None)
        return da.decode_attention_paged(
            q, k, v, pt, n, k_scale=ks, v_scale=vs,
            layer=rest[-1] if stacked else None)
    return fn


def _paged(one_chip, dtype, quantized, layers=None, **shape):
    args = _paged_args(one_chip, dtype, quantized, layers=layers, **shape)
    return _compile_text(_paged_fn(quantized, layers is not None), *args)


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


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
def test_stacked_paged_decode_compiles_at_cell_widths(one_chip, cell,
                                                      quantized):
    """The kernel on the pools of 12 layers stacked, as the decode step's
    layer scan carries them, reads them where they lie: the compiled call
    neither copies nor slices a whole pool or one layer of the payload."""
    dtype = jnp.float32 if quantized else jnp.bfloat16
    shape = CELL_SHAPES[cell]
    text = _paged(one_chip, dtype, quantized, layers=12, **shape)
    assert "tpu_custom_call" in text
    pool = (12, 1 + shape["b"] * shape["w"], shape["kvh"], PS, shape["d"])
    assert whole_buffer_ops(text, [pool, pool[1:], (1, *pool[1:])]) == []


@pytest.mark.parametrize("stacked", [False, True], ids=["pool", "stacked"])
def test_paged_decode_custom_call_keeps_its_name(one_chip, stacked):
    """The benchmark finds the kernel in a device trace by the name of its
    HLO instruction, ``%decode_attention_paged.<n>``, which the jitted
    wrapper in ``kernels/ops.py`` gives it, with or without a layer."""
    args = _paged_args(one_chip, jnp.bfloat16, False,
                       layers=12 if stacked else None,
                       **CELL_SHAPES["qwen3-8b-pp3"])
    layer = {"layer": args.pop()} if stacked else {}
    text = kops.decode_attention_paged.lower(
        *args, **layer, interpret=False).compile().as_text()
    names = [line.split("=")[0].strip() for line in text.splitlines()
             if "tpu_custom_call" in line and "=" in line]
    assert names and all(n.startswith("%decode_attention_paged")
                         for n in names), names


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "qwen3-moe-235b-a22b"])
def test_paged_step_updates_pools_in_place_on_v5e(one_chip, monkeypatch,
                                                  arch):
    """The scheduler's paged bf16 decode step compiled for the chip, with
    the paged kernel and 128-wide heads as in the cells: the donated
    pools alias the step's output, and no instruction copies, slices or
    converts a whole pool or one layer of it (the CPU's compiler widens
    bf16 updates, so ``test_paged.py`` checks float pools there).  The
    pools are large enough (5-19 MB a layer) that the compiler does not
    stage them through the core's own memory, as it does small arrays."""
    # the kernel wrappers pick interpret mode on the CPU backend; a
    # compile for the described chip needs the Mosaic kernel
    monkeypatch.setattr(kops, "_interpret", lambda override: False)
    cfg = dataclasses.replace(reduced(get_config(arch)), head_dim=128)
    s = ContinuousBatchingScheduler(
        cfg, models.init_params(cfg, jax.random.PRNGKey(0)), max_slots=8,
        cache_len=2048, max_new_cap=16, kv_layout="paged", page_size=PS,
        kv_dtype="bf16", attn_backend="pallas")
    st = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    compiled = s._step_fn.lower(st(s.params), st(s.state)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    pools = [v for k, v in s.state["cache"].items() if k.endswith("_pages")]
    shapes = [x for p in pools
              for x in (p.shape, p.shape[1:], (1, *p.shape[1:]))]
    assert whole_buffer_ops(text, shapes) == []
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= sum(p.nbytes for p in pools)


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
