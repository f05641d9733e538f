"""Shared model components: param templates, norms, RoPE, attention, MLP.

Everything is pure-functional JAX.  Parameters are nested dicts of arrays;
their *structure* is described once by a template tree of ``P`` leaves so
that real initialization (``init_params``), abstract shapes for the dry-run
(``param_struct``) and PartitionSpecs (``param_pspecs``) can never drift
apart.
"""
from __future__ import annotations

import dataclasses
import math
import zlib
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.sharding_hints import hint

# ---------------------------------------------------------------------------
# Param templates
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class P:
    """Template for one parameter tensor.

    ``axes`` are *logical* axis names (resolved to mesh axes by
    ``repro.launch.sharding``); ``init`` picks the initializer.
    """
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"          # normal | zeros | ones | embed
    scale: Optional[float] = None

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _leaf_key(key, path) -> jax.Array:
    """The leaf's own key: ``key`` folded with a digest of its path that
    is the same in every process (``hash()`` of a string is not)."""
    h = zlib.crc32(jax.tree_util.keystr(path).encode()) % (2 ** 31)
    return jax.random.fold_in(key, h)


def init_params(template, key, dtype=jnp.float32):
    """Materialize a template tree into real parameter arrays."""

    def init_leaf(path, p: P):
        k = _leaf_key(key, path)
        if p.init == "zeros":
            return jnp.zeros(p.shape, dtype)
        if p.init == "ones":
            return jnp.ones(p.shape, dtype)
        # fan-in is the contraction dim — second-to-last for (possibly
        # layer-stacked) matrices, e.g. (L, d_in, d_out) -> d_in
        fan_in = p.shape[-2] if len(p.shape) > 1 else max(p.shape[-1], 1)
        if p.init == "embed":
            scale = p.scale if p.scale is not None else 0.02
        else:
            scale = p.scale if p.scale is not None else 1.0 / math.sqrt(fan_in)
        return (scale * jax.random.normal(k, p.shape)).astype(dtype)

    return jax.tree_util.tree_map_with_path(
        init_leaf, template, is_leaf=lambda x: isinstance(x, P))


def param_struct(template, dtype=jnp.bfloat16):
    """ShapeDtypeStruct tree for .lower() without allocation."""
    return jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, dtype),
        template, is_leaf=lambda x: isinstance(x, P))


def param_axes(template):
    """Tree of logical-axis tuples, same structure as the params."""
    return jax.tree.map(lambda p: p.axes, template,
                        is_leaf=lambda x: isinstance(x, P))


def param_count_of(template) -> int:
    leaves = jax.tree.leaves(
        jax.tree.map(lambda p: math.prod(p.shape), template,
                     is_leaf=lambda x: isinstance(x, P)))
    return int(sum(leaves))


# ---------------------------------------------------------------------------
# Normalization + activations
# ---------------------------------------------------------------------------


def rms_norm(x, weight, eps=1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * lax.rsqrt(var + eps)
    return (x * (1.0 + weight.astype(jnp.float32))).astype(dtype)


def layer_norm(x, weight, bias, eps=1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


def swiglu(x, w_gate, w_up, w_down):
    g = hint(x @ w_gate, "batch", "seq", "ff")
    u = hint(x @ w_up, "batch", "seq", "ff")
    return hint((jax.nn.silu(g) * u) @ w_down, "batch", "seq", "embed")


def gelu_mlp(x, w_in, b_in, w_out, b_out):
    h = jax.nn.gelu(x @ w_in + b_in)
    return h @ w_out + b_out


# ---------------------------------------------------------------------------
# Rotary position embeddings (llama-style rotate-half)
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)           # (head_dim/2,)


def apply_rope(x, positions, theta: float):
    """x: (..., S, n_heads, head_dim); positions: (..., S) int32."""
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]        # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention — chunked (flash-style) for long sequences, plus decode path
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _repeat_kv(x, groups: int):
    """(B, S, KV, D) -> (B, S, KV*groups, D)"""
    b, s, kv, d = x.shape
    x = jnp.broadcast_to(x[:, :, :, None, :], (b, s, kv, groups, d))
    return x.reshape(b, s, kv * groups, d)


def attention_full(q, k, v, *, causal: bool = True, window: int = 0,
                   q_offset: int = 0):
    """Naive reference attention (materializes scores).

    q: (B, Sq, H, D); k, v: (B, Sk, KV, D).  ``window``>0 restricts each
    query to the last ``window`` keys (sliding window / local attention).
    ``q_offset`` is the absolute position of q[0] relative to k[0].
    """
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    k = _repeat_kv(k, h // kvh)
    v = _repeat_kv(v, h // kvh)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(d)
    qpos = jnp.arange(sq) + q_offset
    kpos = jnp.arange(k.shape[1])
    mask = jnp.ones((sq, k.shape[1]), dtype=bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window:
        mask &= kpos[None, :] > (qpos[:, None] - window)
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out


def attention_chunked(q, k, v, *, causal: bool = True, window: int = 0,
                      q_chunk: int = 1024, k_chunk: int = 1024,
                      q_offset: int = 0, save_memory: bool = False):
    """Flash-style attention in pure JAX: online softmax over KV chunks.

    Peak score memory is (B, H, q_chunk, k_chunk) per step instead of
    (B, H, S, S).  Matches ``attention_full`` to fp32 accuracy; this is the
    path the 32k/500k shapes lower through.  (The Pallas TPU kernel in
    repro.kernels.flash_attention implements the same schedule on-chip.)

    ``save_memory=True`` (§Perf override ``attn_ckpt``) additionally
    rematerializes each q-chunk's scores in the backward pass instead of
    stacking per-chunk residuals to HBM — trading ~1x recompute for ~2x
    score-tensor traffic, the HLO-level analogue of what the Pallas flash
    kernel's fused backward does in VMEM.
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    kvh = k.shape[2]
    groups = h // kvh
    q_chunk = min(q_chunk, sq)
    k_chunk = min(k_chunk, sk)
    if sq % q_chunk or sk % k_chunk:
        return attention_full(q, k, v, causal=causal, window=window,
                              q_offset=q_offset)
    nq, nk = sq // q_chunk, sk // k_chunk
    scale = 1.0 / math.sqrt(d)

    k = k.reshape(b, nk, k_chunk, kvh, d)
    v = v.reshape(b, nk, k_chunk, kvh, d)
    qr = q.reshape(b, nq, q_chunk, h, d)

    def per_qchunk(qi, qc):
        # qc: (B, q_chunk, H, D)
        qpos = qi * q_chunk + jnp.arange(q_chunk) + q_offset

        def body(carry, inputs):
            m, l, acc = carry
            ki, kc, vc = inputs
            kcr = _repeat_kv(kc, groups)      # (B, k_chunk, H, D)
            vcr = _repeat_kv(vc, groups)
            s = jnp.einsum("bqhd,bkhd->bhqk", qc.astype(jnp.float32),
                           kcr.astype(jnp.float32)) * scale
            kpos = ki * k_chunk + jnp.arange(k_chunk)
            mask = jnp.ones((q_chunk, k_chunk), dtype=bool)
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            if window:
                mask &= kpos[None, :] > (qpos[:, None] - window)
            s = jnp.where(mask[None, None], s, NEG_INF)
            # (tried: bf16 score boundary tensors under save_memory —
            # REFUTED, +1% memory term: with the checkpointed body the
            # recompute traffic dominates and XLA's boundaries don't move.
            # See EXPERIMENTS.md §Perf iteration 3.)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            if save_memory:
                # bf16 probs for the PV matmul (flash-kernel practice);
                # the running stats stay fp32
                pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(jnp.bfloat16),
                                vcr.astype(jnp.bfloat16)
                                ).astype(jnp.float32)
            else:
                pv = jnp.einsum("bhqk,bkhd->bhqd", p,
                                vcr.astype(jnp.float32))
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        if save_memory:
            body = jax.checkpoint(body)
        m0 = jnp.full((b, h, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, h, q_chunk, d), jnp.float32)
        ks = jnp.arange(nk)
        (m, l, acc), _ = lax.scan(
            body, (m0, l0, a0),
            (ks, jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return jnp.einsum("bhqd->bqhd", out)

    outs = lax.map(lambda args: per_qchunk(*args),
                   (jnp.arange(nq), jnp.moveaxis(qr, 1, 0)))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, sq, h, d)
    return out.astype(q.dtype)


def attention_decode(q, k_cache, v_cache, valid_len, layout="bskd"):
    """One-token decode attention against a (possibly ring) KV cache.

    q: (B, 1, H, D); caches: (B, S, KV, D) for layout='bskd' (encdec
    legacy) or (B, KV, S, D) for layout='bksd' (decoder-only canonical);
    valid_len: number of valid cache slots (== S once the ring is full) —
    a scalar, or a per-lane (B,) vector for the ragged lane-major batch
    where every lane sits at a different prefix length.  The bksd layout
    makes both decode dots batch-major (b, kv leading), so XLA inserts NO
    cache-slice transpose (§Perf h3 it3).

    The caches are consumed in their storage dtype (bf16) with fp32
    ACCUMULATION (preferred_element_type) — materializing an fp32 copy of
    the cache would double the dominant HBM term of the decode roofline
    (§Perf hillclimb 3; the Pallas kernel in kernels/decode_attention.py
    is the on-chip version of the same rule).
    """
    b, _, h, d = q.shape
    if layout == "bskd":
        s, kvh = k_cache.shape[1], k_cache.shape[2]
        eq_s, eq_o = "bkgd,bskd->bkgs", "bkgs,bskd->bkgd"
    else:
        assert layout == "bksd", layout
        kvh, s = k_cache.shape[1], k_cache.shape[2]
        eq_s, eq_o = "bkgd,bksd->bkgs", "bkgs,bksd->bkgd"
    groups = h // kvh
    qg = q[:, 0].reshape(b, kvh, groups, d)
    scores = jnp.einsum(eq_s, qg.astype(k_cache.dtype), k_cache,
                        preferred_element_type=jnp.float32) / math.sqrt(d)
    valid_len = jnp.asarray(valid_len)
    if valid_len.ndim == 0:
        valid = (jnp.arange(s) < valid_len)[None, None, None, :]
    else:                       # ragged: per-lane (B,) valid prefix
        valid = (jnp.arange(s)[None, :] < valid_len[:, None])[:, None, None]
    scores = jnp.where(valid, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(eq_o, probs.astype(k_cache.dtype),
                     v_cache, preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# KV cache helpers (ring-buffer when the cache is shorter than the stream)
# ---------------------------------------------------------------------------


def cache_write(cache_k, cache_v, k_new, v_new, pos):
    """Write one token at ring position pos % S of (B, KV, S, D) caches;
    ``k_new``/``v_new``: (B, KV, 1, D)."""
    s = cache_k.shape[2]
    idx = jnp.mod(pos, s)
    cache_k = lax.dynamic_update_slice_in_dim(
        cache_k, k_new.astype(cache_k.dtype), idx, axis=2)
    cache_v = lax.dynamic_update_slice_in_dim(
        cache_v, v_new.astype(cache_v.dtype), idx, axis=2)
    return cache_k, cache_v


def cache_write_batch(cache_k, cache_v, k_new, v_new, pos):
    """Per-lane ring write for the lane-major batched decode step.

    ``pos`` is a (B,) vector of absolute positions; lane b's token lands
    at ring slot ``pos[b] % S`` of the (B, KV, S, D) caches.
    ``k_new``/``v_new``: (B, KV, 1, D).
    """
    s = cache_k.shape[2]
    idx = jnp.mod(pos, s)
    rows = jnp.arange(cache_k.shape[0])
    cache_k = cache_k.at[rows, :, idx].set(k_new[:, :, 0].astype(cache_k.dtype))
    cache_v = cache_v.at[rows, :, idx].set(v_new[:, :, 0].astype(cache_v.dtype))
    return cache_k, cache_v


def cache_write_batch_q8(cache_k, cache_v, scale_k, scale_v, k_new, v_new,
                         pos):
    """Quantizing per-lane ring write for the int8 KV cache.

    The incoming token's K/V rows are int8-quantized per (lane, kv-head)
    — one scalar scale over head_dim — and both the payload and the
    slot's scale are scattered at ring slot ``pos[b] % S``.  Per-SLOT
    scales (rather than scales shared across positions) keep every cache
    entry decoded with exactly the scale it was encoded with: a shared
    running-max scale would either misscale earlier tokens when it grows
    or force a full-cache requantization per write — the very traffic
    this cache exists to avoid.  The 4-byte scale adds ``4/D`` bytes per
    int8 row (~6%% at D=64) against the 2x payload saving.

    ``cache_k``/``cache_v``: int8 (B, KV, S, D); ``scale_k``/``scale_v``:
    fp32 (B, KV, S); ``k_new``/``v_new``: float (B, KV, 1, D).
    """
    from repro.core.quantize import quantize_into
    s = cache_k.shape[2]
    idx = jnp.mod(pos, s)
    rows = jnp.arange(cache_k.shape[0])
    kq, ks = quantize_into(k_new[:, :, 0], axis=-1)    # (B,KV,D),(B,KV)
    vq, vs = quantize_into(v_new[:, :, 0], axis=-1)
    cache_k = cache_k.at[rows, :, idx].set(kq)
    cache_v = cache_v.at[rows, :, idx].set(vq)
    scale_k = scale_k.at[rows, :, idx].set(ks)
    scale_v = scale_v.at[rows, :, idx].set(vs)
    return cache_k, cache_v, scale_k, scale_v


def cache_valid_len(pos, cache_size):
    return jnp.minimum(pos + 1, cache_size)


def _paged_slot(page_table, pos, page_size):
    """Resolve per-lane write coordinates in a page pool.

    ``pos`` (B,) absolute positions wrap at the lane's logical capacity
    ``W * page_size`` (mirroring the ring cache's ``pos %% S``), then
    split into (physical page via the lane's table row, offset in page).
    """
    w = page_table.shape[1]
    p = jnp.mod(pos, w * page_size)
    rows = jnp.arange(page_table.shape[0])
    phys = page_table[rows, p // page_size]            # (B,) pool pages
    return phys, p % page_size


def _write_rows(pool, phys, off, rows, layer=None):
    """Write lane b's row ``rows[b]`` ((KV, D) or (KV,)) at ``[phys[b],
    :, off[b]]`` of a pool (P, KV, ps[, D]), or at ``[layer, phys[b], :,
    off[b]]`` of a stacked one (L, P, KV, ps[, D]): one
    ``dynamic_update_slice`` per lane, which XLA performs in place on a
    pool that a scan carries or a jit donates (a scatter with these
    indices lays the pool out anew first)."""
    lead = () if layer is None else (layer,)
    tail = (0,) * (pool.ndim - len(lead) - 3)
    for b in range(rows.shape[0]):
        row = rows[b].astype(pool.dtype)[None, :, None]    # (1, KV, 1[, D])
        pool = lax.dynamic_update_slice(
            pool, row.reshape((1,) * len(lead) + row.shape),
            (*lead, phys[b], 0, off[b], *tail))
    return pool


def cache_write_batch_paged(pool_k, pool_v, page_table, k_new, v_new, pos,
                            layer=None):
    """Per-lane one-token write into a paged KV pool.

    ``pool_k``/``pool_v``: (P, KV, ps, D); ``page_table``: (B, W) int32;
    ``k_new``/``v_new``: (B, KV, 1, D) as in :func:`cache_write_batch`.
    With ``layer`` the pools are every layer's, stacked (L, P, KV, ps, D),
    and each lane's row lands at ``[layer, phys, :, off]``.  The rows are
    written in place (:func:`_write_rows`).
    The allocator guarantees every ACTIVE lane's current page is
    exclusively owned (copy-on-write happens host-side before the step),
    so the writes cannot collide; inactive lanes' table rows are all
    zeros and land in the reserved garbage page 0.
    """
    ps = pool_k.shape[-2]
    phys, off = _paged_slot(page_table, pos, ps)
    return (_write_rows(pool_k, phys, off, k_new[:, :, 0], layer),
            _write_rows(pool_v, phys, off, v_new[:, :, 0], layer))


def cache_write_batch_paged_q8(pool_k, pool_v, scale_k, scale_v, page_table,
                               k_new, v_new, pos, layer=None):
    """Quantizing paged write: int8 payload pools (P, KV, ps, D) plus
    per-slot fp32 scale pools (P, KV, ps) — the paged analogue of
    :func:`cache_write_batch_q8`, same per-(lane, head, slot) scale
    semantics.  ``layer`` as in :func:`cache_write_batch_paged`, the
    scale pools then stacked (L, P, KV, ps)."""
    from repro.core.quantize import quantize_into
    ps = pool_k.shape[-2]
    phys, off = _paged_slot(page_table, pos, ps)
    kq, ks = quantize_into(k_new[:, :, 0], axis=-1)    # (B,KV,D),(B,KV)
    vq, vs = quantize_into(v_new[:, :, 0], axis=-1)
    return tuple(_write_rows(pool, phys, off, rows, layer)
                 for pool, rows in ((pool_k, kq), (pool_v, vq),
                                    (scale_k, ks), (scale_v, vs)))


def decode_attention_named(q, k_cache, v_cache, valid_len, *,
                           backend: Optional[str] = None,
                           k_scale=None, v_scale=None, page_table=None,
                           layer=None):
    """Decode attention through the op-registry named-backend mechanism.

    Caches are (B, KV, S, D) rings or (P, KV, ps, D) page pools; with
    ``layer`` the pools are every layer's stacked, (L, P, KV, ps, D), and
    layer ``layer`` of them is read (paged backends only).
    ``backend`` is a registry backend name — 'ref' (the jnp
    :func:`attention_decode` oracle), 'pallas' (the ragged flash-decode
    kernel in repro.kernels.decode_attention), or None/'auto' (pallas on
    TPU, ref on the CPU).  Same resolution path as the graph ops: adding a
    new decode implementation is one ``REGISTRY.register_backend`` call.

    Passing ``k_scale``/``v_scale`` marks the cache as int8 + per-slot
    scales and resolves the q8 twins of the same backend names
    ('ref_q8' oracle | 'pallas_q8' in-kernel dequant).  Passing
    ``page_table`` marks ``k_cache``/``v_cache`` (and the scales) as
    page POOLS and resolves the paged twins ('paged_ref' | 'paged');
    both markers compose ('paged_ref_q8' | 'paged_q8').
    """
    from repro.core.ops import REGISTRY, resolve_decode_backend
    quantized = k_scale is not None
    paged = page_table is not None
    fn = REGISTRY.op("decode_attention").backend(
        resolve_decode_backend(backend, quantized=quantized, paged=paged))
    kw = {}
    if quantized:
        kw.update(k_scale=k_scale, v_scale=v_scale)
    if paged:
        kw.update(page_table=page_table)
    if layer is not None:
        assert paged, "a layer index addresses stacked page pools"
        kw.update(layer=layer)
    return fn(q, k_cache, v_cache, valid_len, **kw)


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def softmax_xent(logits, labels, mask=None):
    """Mean next-token cross entropy. logits (B,S,V), labels (B,S)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - ll
    if mask is None:
        return nll.mean()
    mask = mask.astype(jnp.float32)
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
