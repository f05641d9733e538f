"""Public jit'd wrappers for all Pallas kernels.

On a TPU every kernel compiles to a Mosaic kernel, and asking for
interpret mode there is an error.  On the CPU (the test backend) every
kernel runs in ``interpret=True`` mode — the kernel body executes as
traced jnp, which is how correctness is validated off the chip.  Any
other backend has no Pallas path here and raises.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import (conv2d as _conv2d_mod, decode_attention as _da,
                           elementwise as _ew, flash_attention as _fa,
                           grouped_ffn as _gf, int8_matmul as _i8,
                           matmul as _mm, pool as _pool,
                           rwkv6_chunk as _rwkv, softmax as _sm)


def _interpret(override: Optional[bool]) -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        if override:
            raise ValueError("interpret=True on a TPU: the kernel would be "
                             "emulated instead of compiled")
        return False
    if backend == "cpu":
        return True if override is None else override
    raise RuntimeError(f"no Pallas kernel path for backend {backend!r}")


# thin wrappers (jit applied here so benchmarks measure steady-state)

@functools.partial(jax.jit, static_argnames=("stride", "pad", "activation",
                                             "interpret"))
def conv2d(x, w, b=None, *, stride=1, pad=0, activation="none",
           interpret=None):
    return _conv2d_mod.conv2d(x, w, b, stride=stride, pad=pad,
                              activation=activation,
                              interpret=_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("activation", "interpret",
                                             "block_m", "block_n", "block_k"))
def matmul(a, b, bias=None, *, activation="none", interpret=None,
           block_m=256, block_n=256, block_k=512):
    return _mm.matmul(a, b, bias=bias, activation=activation,
                      block_m=block_m, block_n=block_n, block_k=block_k,
                      interpret=_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("mode", "kernel", "stride",
                                             "pad", "interpret"))
def pool2d(x, *, mode="max", kernel=2, stride=2, pad=0, interpret=None):
    return _pool.pool2d(x, mode=mode, kernel=kernel, stride=stride, pad=pad,
                        interpret=_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def softmax(x, *, interpret=None):
    return _sm.softmax(x, interpret=_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("act", "interpret"))
def elementwise(x, act="relu", *, interpret=None):
    return _ew.elementwise(x, act, interpret=_interpret(interpret))


def relu(x, *, interpret=None):
    return elementwise(x, "relu", interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def int8_matmul(a_q, b_q, a_scale, b_scale, *, interpret=None):
    return _i8.int8_matmul(a_q, b_q, a_scale, b_scale,
                           interpret=_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, block_q=256,
                    block_k=256, interpret=None):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                              "block_k", "interpret"))
def flash_attention_trainable(q, k, v, *, causal=True, window=0,
                              block_q=256, block_k=256, interpret=None):
    """Differentiable flash attention with FUSED Pallas forward+backward
    (custom VJP; saves only O and logsumexp, recomputes p in VMEM)."""
    from repro.kernels import flash_attention_bwd as _fab
    return _fab.flash_attention_trainable(
        q, k, v, causal, window, block_q, block_k, _interpret(interpret))


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_attention(q, k, v, valid_len, *, block_s=512, interpret=None):
    return _da.decode_attention(q, k, v, valid_len, block_s=block_s,
                                interpret=_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_attention_q8(q, k, v, k_scale, v_scale, valid_len, *,
                        block_s=512, interpret=None):
    """Int8-cache flash-decode: k/v are int8 payloads dequantized inside
    the block loop with per-(lane, head, slot) fp32 scales."""
    return _da.decode_attention(q, k, v, valid_len, block_s=block_s,
                                k_scale=k_scale, v_scale=v_scale,
                                interpret=_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_attention_paged(q, k, v, page_table, valid_len, *, layer=None,
                           interpret=None):
    """Paged flash-decode: K/V live in a global page pool, each lane's
    int32 page-table row names the physical pages its valid slots fill;
    the kernel copies only those pages, one DMA per page of all KV heads.
    With ``layer`` the pools are every layer's, stacked, and the kernel
    reads that layer's pages in place."""
    return _da.decode_attention_paged(q, k, v, page_table, valid_len,
                                      layer=layer,
                                      interpret=_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_attention_paged_q8(q, k, v, k_scale, v_scale, page_table,
                              valid_len, *, layer=None, interpret=None):
    """Paged int8 flash-decode: page-table indirection over int8 payload
    pools AND their per-slot fp32 scale pools, dequantized per block in
    VMEM.  ``layer`` as in :func:`decode_attention_paged`."""
    return _da.decode_attention_paged(q, k, v, page_table, valid_len,
                                      k_scale=k_scale, v_scale=v_scale,
                                      layer=layer,
                                      interpret=_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("tile", "chunks", "interpret"))
def grouped_expert_ffn(x, w_gate, w_up, w_down, start, rows, layer, *, tile,
                       chunks, interpret=None):
    """Grouped SwiGLU over the held experts of layer ``layer`` of the
    stacked weights: each expert's weights stream once per chunk of its
    rows, and only its routed rows are computed (``kernels/grouped_ffn``)."""
    return _gf.grouped_ffn(x, w_gate, w_up, w_down, start, rows, layer,
                           tile=tile, chunks=chunks,
                           interpret=_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_chunked(r, k, v, w, u, *, chunk=16, interpret=None):
    t = r.shape[1]
    pad = (-t) % chunk
    if pad:
        zp = ((0, 0), (0, pad), (0, 0), (0, 0))
        r, k, v = jnp.pad(r, zp), jnp.pad(k, zp), jnp.pad(v, zp)
        w = jnp.pad(w, zp, constant_values=1.0)
    out, s = _rwkv.rwkv6_chunked(r, k, v, w, u, chunk=chunk,
                                 interpret=_interpret(interpret))
    return out[:, :t], s
