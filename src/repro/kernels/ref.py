"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

Each ``*_ref`` matches the corresponding wrapper in repro.kernels.ops
bit-for-bit up to fp accumulation order; tests sweep shapes/dtypes and
assert_allclose kernel-vs-ref.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

# conv/pool oracles live with the graph engine — re-export for tests
from repro.core.graph import conv2d_ref, pool2d_ref  # noqa: F401


def matmul_ref(a, b, *, bias=None, activation: str = "none"):
    out = jnp.dot(a, b, preferred_element_type=jnp.float32)
    if bias is not None:
        out = out + bias
    if activation == "relu":
        out = jax.nn.relu(out)
    elif activation == "silu":
        out = jax.nn.silu(out)
    elif activation == "gelu":
        out = jax.nn.gelu(out)
    return out.astype(a.dtype)


def softmax_ref(x):
    return jax.nn.softmax(x.astype(jnp.float32), axis=-1).astype(x.dtype)


def relu_ref(x):
    return jax.nn.relu(x)


def int8_matmul_ref(a_q, b_q, a_scale, b_scale):
    """a_q: (M, K) int8; b_q: (K, N) int8; scales: (M,), (N,) fp32.

    Dequantized result: (a_q * a_scale[:,None]) @ (b_q * b_scale[None,:]).
    """
    acc = jnp.dot(a_q.astype(jnp.int32), b_q.astype(jnp.int32),
                  preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * a_scale[:, None] * b_scale[None, :]


def decode_attention_ref(q, k, v, valid_len):
    """q: (B, H, D); k, v: (B, KV, S, D); valid_len: scalar int or
    per-lane (B,) vector."""
    b, h, d = q.shape
    kvh, s = k.shape[1], k.shape[2]
    g = h // kvh
    qg = q.reshape(b, kvh, g, d)
    scores = jnp.einsum("bkgd,bksd->bkgs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(d)
    scores = jnp.where(_valid_mask(valid_len, s), scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bksd->bkgd", probs, v.astype(jnp.float32))
    return out.reshape(b, h, d).astype(q.dtype)


def _valid_mask(valid_len, s):
    """(B|1, 1, 1, S) mask of the slots below each lane's valid length."""
    valid = jnp.asarray(valid_len)
    if valid.ndim == 0:
        return (jnp.arange(s) < valid)[None, None, None]
    return (jnp.arange(s)[None, :] < valid[:, None])[:, None, None]


def decode_attention_q8_ref(q, k_q, v_q, k_scale, v_scale, valid_len):
    """Ragged q8 decode oracle: int8 K/V payloads + one fp32 scale per
    (lane, kv-head, ring slot), fp32 accumulation throughout.

    q: (B, H, D); k_q, v_q: int8 (B, KV, S, D); k_scale, v_scale:
    (B, KV, S); valid_len: scalar int or per-lane (B,) vector.

    Scales are applied in the SAME order as the Pallas kernel — K scales
    multiply the score columns after the QK dot, V scales fold into the
    probability rows before the PV dot — so kernel-vs-ref agreement is
    limited only by the online-softmax accumulation order.
    """
    b, h, d = q.shape
    kvh, s = k_q.shape[1], k_q.shape[2]
    g = h // kvh
    qg = q.reshape(b, kvh, g, d)
    scores = jnp.einsum("bkgd,bksd->bkgs", qg.astype(jnp.float32),
                        k_q.astype(jnp.float32)) / math.sqrt(d)
    # (B, KV, S) -> (B, KV, 1, S) broadcast over the g query heads
    scores = scores * k_scale.astype(jnp.float32)[:, :, None]
    scores = jnp.where(_valid_mask(valid_len, s), scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = probs * v_scale.astype(jnp.float32)[:, :, None]
    out = jnp.einsum("bkgs,bksd->bkgd", probs, v_q.astype(jnp.float32))
    return out.reshape(b, h, d).astype(q.dtype)


def paged_gather(pool, page_table, layer=None):
    """Gather a lane-major ring-equivalent cache out of a page pool.

    pool: (P, KV, ps, D) payloads — or the scale pools (P, KV, ps);
    page_table: (B, W) int32.  Returns the (B, KV, W*ps, D)-shaped
    (resp. (B, KV, W*ps)) array in which lane b's logical slot t is
    ``pool[page_table[b, t // ps]][:, t % ps]`` — a pure memory reorder,
    so any ring-cache oracle applied to the gather is bit-identical to
    true paged attention.  With ``layer`` the pool is every layer's,
    stacked, and the pages of layer ``layer`` are gathered.
    """
    # (B, W, KV, ps[, D])
    g = pool[page_table] if layer is None else pool[layer, page_table]
    b, w = g.shape[:2]
    g = jnp.moveaxis(g, 1, 2)           # (B, KV, W, ps[, D])
    return g.reshape(b, g.shape[1], w * g.shape[3], *g.shape[4:])


def decode_attention_paged_ref(q, k_pool, v_pool, page_table, valid_len,
                               layer=None):
    """Paged decode oracle: gather pages into the equivalent ring layout
    and reuse the ragged ring oracle.  q: (B, H, D); pools (and
    ``layer``) as in :func:`paged_gather`; valid_len counts LOGICAL slots
    (< W*ps)."""
    k = paged_gather(k_pool, page_table, layer)
    v = paged_gather(v_pool, page_table, layer)
    return decode_attention_ref(q, k, v, valid_len)


def decode_attention_paged_q8_ref(q, k_pool, v_pool, k_scale, v_scale,
                                  page_table, valid_len, layer=None):
    """Paged int8 decode oracle: gather payload AND per-slot scale pools
    through the page table, then reuse the ragged q8 ring oracle."""
    k = paged_gather(k_pool, page_table, layer)
    v = paged_gather(v_pool, page_table, layer)
    ks = paged_gather(k_scale, page_table, layer)
    vs = paged_gather(v_scale, page_table, layer)
    return decode_attention_q8_ref(q, k, v, ks, vs, valid_len)


def grouped_ffn_ref(x, w_gate, w_up, w_down, start, rows, layer, *,
                    tile: int):
    """Oracle of ``kernels/grouped_ffn``: each expert of layer ``layer``
    applies its SwiGLU to the live rows of its group (block ``start[e]``,
    ``rows[e]`` rows); zeros on every other row."""
    w_gate, w_up, w_down = w_gate[layer], w_up[layer], w_down[layer]
    r = jnp.arange(x.shape[0])
    out = jnp.zeros(x.shape, jnp.float32)
    for e in range(w_gate.shape[0]):
        lo = start[e] * tile
        g = jnp.dot(x, w_gate[e], preferred_element_type=jnp.float32)
        u = jnp.dot(x, w_up[e], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(w_down.dtype)
        y = jnp.dot(h, w_down[e], preferred_element_type=jnp.float32)
        out = jnp.where(((r >= lo) & (r < lo + rows[e]))[:, None], y, out)
    return out.astype(x.dtype)


def flash_attention_ref(q, k, v, *, causal=True, window=0):
    """q: (B, S, H, D); k, v: (B, S, KV, D) — full-sequence attention."""
    from repro.models.common import attention_full
    return attention_full(q, k, v, causal=causal, window=window)


def rwkv6_ref(r, k, v, w, u, s0=None):
    """Token-by-token RWKV6 recurrence (B, T, H, N)."""
    from repro.models.rwkv6 import wkv_scan
    return wkv_scan(r, k, v, w, u, s0=s0)
