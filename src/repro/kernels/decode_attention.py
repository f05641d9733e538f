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

Paged caches: :func:`decode_attention_paged` reads K/V from a global
page POOL instead of per-lane rings.  The pool drops the batch axis —
``(P, KV, ps, D)`` — and each lane owns a row of an int32 ``page_table``
``(B, W)`` mapping its logical page ``j`` to a physical pool page, so one
physical page of all KV heads is one contiguous ``(KV, ps, D)`` slab.
The kernel does not use the ring kernel's grid: it runs one grid step
per lane, and the pools stay in HBM.  Inside, a loop runs over the
lane's ``ceil(ceil(valid / ps) / pages_per_block)`` blocks; each block
is one DMA per valid page (the slab of all KV heads, and the page's
int8 scale row), double-buffered so that the next block's copies —
this lane's, or the next lane's first — run while this block is
computed head by head.  Pages past a lane's end cost neither a DMA nor
a grid step, so the work follows each lane's valid pages, not ``W``.
``pages_per_block`` comes from the shapes (about 1024 tokens within
8 MB of VMEM).  The q8 twin copies the scale rows beside the payload
and dequantizes each block in VMEM.
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


# A paged block holds about this many tokens, and its two K and V buffers
# (with the int8 scale buffers) stay within this much VMEM.  Each block
# and each KV head in it costs a fixed time besides its bytes: on a TPU
# v5e, 1024-token blocks took 15% less time than 512-token ones at long
# contexts, and computing in 128- or 256-token steps took 25-55% more.
_BLOCK_TOKENS = 1024
_BLOCK_VMEM = 8 << 20
_LANE_WIDTH = 128      # a DMA moves whole rows of 128 lanes


def _round_up_lanes(n: int) -> int:
    return pl.cdiv(n, _LANE_WIDTH) * _LANE_WIDTH


def _pages_per_block(kvh: int, ps: int, d: int, w: int, itemsize: int,
                     quantized: bool) -> int:
    """Pool pages one block of the paged kernel copies and computes on:
    about ``_BLOCK_TOKENS`` tokens, no more than a lane's table holds, and
    the double-buffered K and V pages (with their int8 scale rows) within
    ``_BLOCK_VMEM``."""
    page = 2 * kvh * ps * _round_up_lanes(d) * itemsize
    if quantized:
        page += 2 * _round_up_lanes(kvh * ps) * 4
    return max(1, min(w, _BLOCK_TOKENS // ps, _BLOCK_VMEM // (2 * page)))


def _pad_last(x, n):
    """Zero-pad the last axis of ``x`` to ``n``."""
    if x.shape[-1] == n:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, n - x.shape[-1])])


def _paged_kernel(valid_ref, pt_ref, layer_ref, q_ref, k_hbm, v_hbm, *rest,
                  scale, ps, ppb, w, quantized, qk_dtype):
    # K/V are read at the given layer of the stacked pools; the scale rows
    # come as that layer's pool alone (see the wrapper)
    if quantized:
        (ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf, sem, slot_ref,
         m_ref, l_ref, acc_ref) = rest
        pairs = ((k_hbm, k_buf, True), (v_hbm, v_buf, True),
                 (ks_hbm, ks_buf, False), (vs_hbm, vs_buf, False))
    else:
        (o_ref, k_buf, v_buf, sem, slot_ref, m_ref, l_ref, acc_ref) = rest
        pairs = ((k_hbm, k_buf, True), (v_hbm, v_buf, True))
    lane = pl.program_id(0)
    n_lanes = pl.num_programs(0)
    kvh, d = k_buf.shape[2], k_buf.shape[4]
    bt = ppb * ps

    def n_pages(b):
        return jnp.minimum(pl.cdiv(valid_ref[b], ps), w)

    def block_copies(b, blk, slot, *, wait):
        # one DMA per valid page and operand: the page's slab of all KV
        # heads, contiguous in the pool; pages past the lane's end are
        # never copied
        first = blk * ppb

        def page(j, carry):
            src_page = pt_ref[b, first + j]
            for src, dst, stacked in pairs:
                src = src.at[layer_ref[0]] if stacked else src
                cp = pltpu.make_async_copy(src.at[src_page], dst.at[slot, j],
                                           sem.at[slot])
                if wait:
                    cp.wait()
                else:
                    cp.start()
            return carry

        jax.lax.fori_loop(0, jnp.minimum(n_pages(b) - first, ppb), page, 0)

    def slot_scales(buf, slot, h):
        # head h's per-slot scales as a (bt, d) column broadcast
        sc = buf[slot, :, 0, h * ps:(h + 1) * ps]              # (ppb, ps)
        return jnp.broadcast_to(sc[:, :, None], (ppb, ps, d)).reshape(bt, d)

    @pl.when(lane == 0)
    def _first_lane():
        slot_ref[0] = 0
        # the pages of a lane's last block past its end are not copied:
        # they hold zeros until a copy lands, then finite pool data, which
        # the masked (zero) probabilities cancel in the PV dot
        v_buf[...] = jnp.zeros_like(v_buf)
        if quantized:
            vs_buf[...] = jnp.zeros_like(vs_buf)

    npg = n_pages(lane)
    nblk = pl.cdiv(npg, ppb)
    # the previous lane's last block started this lane's first block
    prefetched = jnp.logical_and(lane > 0,
                                 n_pages(jnp.maximum(lane - 1, 0)) > 0)

    @pl.when(jnp.logical_and(npg > 0, jnp.logical_not(prefetched)))
    def _start_first():
        block_copies(lane, 0, slot_ref[0], wait=False)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    valid = valid_ref[lane]

    def block(blk, slot):
        nxt = 1 - slot

        # double buffering: the next block's copies (this lane's, or the
        # next lane's first) run while this block is computed
        @pl.when(blk + 1 < nblk)
        def _prefetch_own():
            block_copies(lane, blk + 1, nxt, wait=False)

        @pl.when(jnp.logical_and(blk + 1 == nblk, lane + 1 < n_lanes))
        def _prefetch_next_lane():
            block_copies(jnp.minimum(lane + 1, n_lanes - 1), 0, nxt,
                         wait=False)

        block_copies(lane, blk, slot, wait=True)
        spos = blk * bt + jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1)
        for h in range(kvh):
            q = q_ref[0, h].astype(qk_dtype)                   # (G, d)
            k = k_buf[slot, :, h].astype(qk_dtype).reshape(bt, d)
            v = v_buf[slot, :, h].astype(jnp.float32).reshape(bt, d)
            if quantized:
                # int8 payloads dequantized by their per-slot scales
                k = k * slot_scales(ks_buf, slot, h)
                v = v * slot_scales(vs_buf, slot, h)
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
            s = jnp.where(spos < valid, s * scale, NEG_INF)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + p.sum(axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new
        return nxt

    slot_ref[0] = jax.lax.fori_loop(0, nblk, block, slot_ref[0])
    o_ref[0] = (acc_ref[...] /
                jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def decode_attention_paged(q, k, v, page_table, valid_len, *,
                           interpret: bool = False, k_scale=None,
                           v_scale=None, layer=None):
    """Flash-decode against a paged KV pool.

    q: (B, H, D); k, v: page pools (P, KV, ps, D) (``P`` physical pages
    of ``ps`` sequence slots); page_table: (B, W) int32 mapping each
    lane's logical page j to a pool page (logical position ``t`` of lane
    ``b`` lives at ``k[page_table[b, t // ps], :, t % ps]``); valid_len:
    scalar or per-lane (B,) count of valid logical slots.

    With ``k_scale``/``v_scale`` ((P, KV, ps) fp32 scale pools) the
    payload pools are int8, dequantized per slot inside the block loop.

    With ``layer`` (an int32 scalar) ``k``/``v`` and the scales are the
    pools of every layer stacked, (L, P, KV, ps, D) and (L, P, KV, ps),
    and the kernel reads layer ``layer`` of them: the index is a scalar
    prefetch and each page DMA reads ``pool[layer, page]`` from HBM, so a
    layer scan that carries the whole stack copies nothing before the
    call.  Heads narrower than 128 lanes are padded, and then only the
    layer's pools are taken and padded.

    One grid step per lane; each lane reads its ``ceil(valid_len / ps)``
    valid pages once, one DMA per page of all KV heads, in blocks of
    pages double-buffered across blocks and lanes.
    """
    quantized = k_scale is not None
    if quantized:
        assert v_scale is not None
    pools = [k, v] + ([k_scale, v_scale] if quantized else [])
    b, h, d = q.shape
    # narrower heads are zero-padded to whole lane rows: zero query lanes
    # add nothing to the scores, zero value lanes are cut from the output
    dp = _round_up_lanes(d)
    if layer is None or dp != d:
        if layer is not None:
            pools = [x[layer] for x in pools]
        pools, layer = [x[None] for x in pools], 0
    k, v = pools[:2]
    n_pool, kvh, ps = k.shape[1:4]
    w = page_table.shape[1]
    g = h // kvh
    qg = _pad_last(q.reshape(b, kvh, g, d), dp)
    operands = [qg, _pad_last(k, dp), _pad_last(v, dp)]
    if quantized:
        # the scales of a page's KV heads as one padded row: the DMA of a
        # (KV, ps) fp32 slab narrower than 128 lanes does not compile, so
        # the layer's scale pools are taken and laid out here
        sp = _round_up_lanes(kvh * ps)
        operands += [_pad_last(x[layer].astype(jnp.float32)
                                .reshape(n_pool, 1, kvh * ps), sp)
                     for x in pools[2:]]
    ppb = _pages_per_block(kvh, ps, d, w, k.dtype.itemsize, quantized)
    # bf16 queries against bf16 keys: the bf16 MXU products are exact, as
    # an f32 upcast's would be
    qk_dtype = (jnp.bfloat16 if q.dtype == k.dtype == jnp.bfloat16
                else jnp.float32)
    valid = jnp.broadcast_to(
        jnp.asarray(valid_len, jnp.int32).reshape(-1), (b,))

    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    lane_spec = pl.BlockSpec((1, kvh, g, dp),
                             lambda bi, vr, pr, lr: (bi, 0, 0, 0))
    scratch = [pltpu.VMEM((2, ppb, kvh, ps, dp), k.dtype),
               pltpu.VMEM((2, ppb, kvh, ps, dp), v.dtype)]
    if quantized:
        scratch += [pltpu.VMEM((2, ppb, 1, sp), jnp.float32)] * 2
    scratch += [pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((kvh, g, 1), jnp.float32),
                pltpu.VMEM((kvh, g, 1), jnp.float32),
                pltpu.VMEM((kvh, g, dp), jnp.float32)]

    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=1.0 / math.sqrt(d), ps=ps,
                          ppb=ppb, w=w, quantized=quantized,
                          qk_dtype=qk_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[lane_spec] + [hbm] * (len(operands) - 1),
            out_specs=lane_spec,
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, dp),
                                       jnp.float32 if quantized else q.dtype),
        # lanes run in order: each lane's last block starts the next
        # lane's first copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(valid, page_table.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), *operands)
    return out[..., :d].reshape(b, h, d).astype(q.dtype)
