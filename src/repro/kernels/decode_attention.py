"""Flash-decode: one-token attention against long (possibly ragged) KV caches.

Beyond-paper kernel for the decode_32k / long_500k shapes AND the
continuous-batching serving hot path: the KV cache is streamed through
VMEM in blocks along the sequence (grid-innermost, so sequential with
scratch carry), with online softmax over the valid prefix.  GQA is
handled by processing all G query heads of one KV head together — the
(G, D) query tile rides along the whole stream, maximizing cache-byte
reuse (the decode bottleneck is HBM bandwidth on cache reads).

Ragged batching (PR 2): ``valid_len`` may be a per-lane ``(B,)`` vector,
so one kernel launch serves a continuous-batching step where every lane
sits at a different position in its ring cache.  Two mechanisms keep the
cost proportional to each lane's actual prefix instead of ``B x S``:

  * the valid vector rides in as a *scalar-prefetch* operand
    (``PrefetchScalarGridSpec``), so the K/V BlockSpec index maps can
    clamp the sequence index to the lane's last useful block — revisiting
    the same block index makes the pipeline skip the HBM->VMEM copy
    entirely for blocks beyond the prefix;
  * the flash update is wrapped in ``@pl.when(si * bs < valid)`` so the
    skipped blocks also cost no MXU flops (block-level early exit).

Quantized caches (PR 6): pass ``k_scale``/``v_scale`` and the K/V
operands are consumed as int8 with one fp32 scale per (lane, kv-head,
ring slot), dequantized INSIDE the block loop — HBM streams half the
bytes of bf16 and the fp32 math is unchanged.  The per-slot (not
per-channel) scale granularity is what lets dequant fold into the
existing dots with zero layout churn:

    scores = (q . k_int^T) * k_scale[slot]      (scale applied to the
                                                 score column, after the
                                                 MXU dot)
    out   += (p * v_scale[slot]) . v_int        (scale folded into the
                                                 probability row, before
                                                 the MXU dot)

so dequant costs two elementwise multiplies on (G, bs) tiles — no
transposes, no materialized fp copy of the cache — and composes with the
block skipping above (skipped blocks also skip their scale DMA).

Layout: K/V are ``(B, KV, S, D)`` (``bksd``, the serving ring-cache
layout, consumed without any transpose); int8 scales are ``(B, KV, S)``.
Inside the call the scales ride as ``(B, KV, 1, S)`` so that their
``(1, bs)`` block spans the full unit axis, which the TPU's (8, 128)
block-tiling rule accepts (a ``(1, 1, bs)`` block of the 3-D array does
not).

Paged caches (PR 7): :func:`decode_attention_paged` reads K/V from a
global page POOL instead of per-lane rings.  The pool drops the batch
axis — ``(P, KV, ps, D)`` — and each lane owns a row of an int32
``page_table`` ``(B, W)`` mapping its logical page ``j`` to a physical
pool page.  The page table rides in as
a SECOND scalar-prefetch operand, so the only change versus the ring
kernel is one extra indirection inside the K/V index maps:

    ring :  block  si  of lane bi  ->  k[bi, :, clamp(si), :]
    paged:  block  si  of lane bi  ->  k_pool[pt[bi, clamp(si)], :, :, :]

The page size IS the KV block size (one grid step = one page), so the
ragged machinery composes unchanged: the clamp pins out-of-prefix steps
to the lane's last useful PAGE (revisited index -> the pipeline skips
the HBM->VMEM copy) and ``@pl.when`` skips their flops.  Physical pages
may be arbitrarily scattered/fragmented in the pool — the index map is
the gather.  The q8 twin indirects the scale pools the same way.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(valid_ref, q_ref, k_ref, v_ref, *rest,
                   scale, bs, ns, quantized):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    bi = pl.program_id(0)
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lane_valid = valid_ref[bi]

    # block-level early exit: blocks entirely beyond this lane's valid
    # prefix contribute nothing — skip the whole flash update (the index
    # maps below also pin their DMA to the last useful block)
    @pl.when(si * bs < lane_valid)
    def _flash_update():
        q = q_ref[0, 0].astype(jnp.float32)            # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)            # (bs, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if quantized:
            # per-slot K scales dequantize the score COLUMNS — a (1, bs)
            # row broadcast over (G, bs), no transpose
            s = s * ks_ref[0, 0]
        spos = si * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(spos < lane_valid, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        if quantized:
            # per-slot V scales fold into the probability rows before the
            # PV dot: p . diag(vs) . v_int == (p * vs) . v_int
            p = p * vs_ref[0, 0]
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(si == ns - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _scale_rows(scale):
    """(..., KV, S) fp32 scales -> (..., KV, 1, S): the unit axis makes a
    ``(1, bs)`` block legal under the TPU's (8, 128) tiling rule."""
    return scale.astype(jnp.float32)[..., None, :]


def decode_attention(q, k, v, valid_len, *, block_s: int = 512,
                     interpret: bool = False, k_scale=None, v_scale=None):
    """q: (B, H, D); k, v: (B, KV, S, D); valid_len: scalar int32 or a
    per-lane (B,) vector (each entry >= 1 — the number of valid ring
    slots, counted from slot 0).

    When ``k_scale``/``v_scale`` ((B, KV, S) fp32) are given, k/v are
    int8 payloads dequantized per ring slot inside the block loop (the
    ``pallas_q8`` backend).
    """
    quantized = k_scale is not None
    if quantized:
        assert v_scale is not None
    b, h, d = q.shape
    kvh, s = k.shape[1], k.shape[2]
    g = h // kvh
    bs = min(block_s, s)
    pad = (-s) % bs
    if pad:
        zp = ((0, 0), (0, 0), (0, pad), (0, 0))
        k, v = jnp.pad(k, zp), jnp.pad(v, zp)
        if quantized:
            k_scale = jnp.pad(k_scale, zp[:3])
            v_scale = jnp.pad(v_scale, zp[:3])
    ns = (s + pad) // bs
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(b, kvh, g, d)
    valid = jnp.broadcast_to(
        jnp.asarray(valid_len, jnp.int32).reshape(-1), (b,))

    # clamp the seq block index to each lane's last useful block: the
    # pipeline skips the copy when the index does not change, so blocks
    # beyond the prefix cost no HBM reads
    def _clamp(si, valid_ref, bi):
        last = jnp.maximum(pl.cdiv(valid_ref[bi], bs) - 1, 0)
        return jnp.minimum(si, last)

    kv_spec = pl.BlockSpec(
        (1, 1, bs, d), lambda bi, ki, si, vr: (bi, ki, _clamp(si, vr, bi), 0))
    sc_spec = pl.BlockSpec(
        (1, 1, 1, bs), lambda bi, ki, si, vr: (bi, ki, 0, _clamp(si, vr, bi)))
    in_specs = [
        pl.BlockSpec((1, 1, g, d),
                     lambda bi, ki, si, vr: (bi, ki, 0, 0)),
        kv_spec,
        kv_spec,
    ]
    operands = [valid, qg, k, v]
    if quantized:
        in_specs += [sc_spec, sc_spec]
        operands += [_scale_rows(k_scale), _scale_rows(v_scale)]

    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, bs=bs, ns=ns,
                          quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, kvh, ns),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, g, d),
                                   lambda bi, ki, si, vr: (bi, ki, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, d),
                                       jnp.float32 if quantized else q.dtype),
        interpret=interpret,
    )(*operands)
    return out.reshape(b, h, d).astype(q.dtype)


def _paged_kernel(valid_ref, pt_ref, *args, **kw):
    """Paged twin of :func:`_decode_kernel`: the page table is consumed
    entirely by the index maps, so the body is the ring kernel's —
    only the leading scalar-prefetch ref is skipped."""
    del pt_ref
    _decode_kernel(valid_ref, *args, **kw)


def decode_attention_paged(q, k, v, page_table, valid_len, *,
                           interpret: bool = False, k_scale=None,
                           v_scale=None):
    """Flash-decode against a paged KV pool.

    q: (B, H, D); k, v: page pools (P, KV, ps, D) (``P`` physical pages
    of ``ps`` sequence slots); page_table: (B, W) int32 mapping each
    lane's logical page j to a pool page (logical position ``t`` of lane
    ``b`` lives at ``k[page_table[b, t // ps], :, t % ps]``); valid_len:
    scalar or per-lane (B,) count of valid logical slots.

    With ``k_scale``/``v_scale`` ((P, KV, ps) fp32 scale pools) the
    payload pools are int8, dequantized per slot inside the block loop
    exactly as in the ring kernel.

    The block size is the page size, so every lane reads exactly
    ``ceil(valid_len / ps)`` pages — fragmentation in the pool costs
    nothing (the index map IS the gather) and pages beyond the prefix
    are skipped by the same clamp + ``pl.when`` early exit as the ring
    path.
    """
    quantized = k_scale is not None
    if quantized:
        assert v_scale is not None
    b, h, d = q.shape
    kvh, ps = k.shape[1], k.shape[2]
    w = page_table.shape[1]
    g = h // kvh
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(b, kvh, g, d)
    valid = jnp.broadcast_to(
        jnp.asarray(valid_len, jnp.int32).reshape(-1), (b,))
    pt = page_table.astype(jnp.int32)

    def _page(si, valid_ref, pt_ref, bi):
        # clamp to the lane's last useful LOGICAL page, then translate to
        # the physical pool page — revisited physical indices make the
        # pipeline skip the copy, exactly as the ring clamp does
        last = jnp.maximum(pl.cdiv(valid_ref[bi], ps) - 1, 0)
        return pt_ref[bi, jnp.minimum(si, last)]

    kv_spec = pl.BlockSpec(
        (1, 1, ps, d),
        lambda bi, ki, si, vr, pr: (_page(si, vr, pr, bi), ki, 0, 0))
    sc_spec = pl.BlockSpec(
        (1, 1, 1, ps),
        lambda bi, ki, si, vr, pr: (_page(si, vr, pr, bi), ki, 0, 0))
    in_specs = [
        pl.BlockSpec((1, 1, g, d),
                     lambda bi, ki, si, vr, pr: (bi, ki, 0, 0)),
        kv_spec,
        kv_spec,
    ]
    operands = [valid, pt, qg, k, v]
    if quantized:
        in_specs += [sc_spec, sc_spec]
        operands += [_scale_rows(k_scale), _scale_rows(v_scale)]

    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, bs=ps, ns=w,
                          quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, kvh, w),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, g, d),
                                   lambda bi, ki, si, vr, pr: (bi, ki, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, d),
                                       jnp.float32 if quantized else q.dtype),
        interpret=interpret,
    )(*operands)
    return out.reshape(b, h, d).astype(q.dtype)
