"""Dense decoder-only transformer (llama/qwen/tinyllama/chameleon families).

Stacked-layer parameters + ``lax.scan`` over layers keep the HLO compact
(one layer body regardless of depth) — this is what makes the 48-layer
34B dry-run compile quickly.  The VLM family (chameleon) is this model:
early fusion means image VQ codes are ordinary ids in the shared vocab.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig
from repro.models import common as cm
from repro.models.common import P
from repro.sharding_hints import hint


# ---------------------------------------------------------------------------
# Templates
# ---------------------------------------------------------------------------


def _attn_template(cfg: ArchConfig, L: int) -> Dict[str, P]:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    t = {
        "ln1": P((L, d), (None, None), "zeros"),
        "wq": P((L, d, cfg.q_dim), (None, "fsdp", "tp_heads")),
        "wk": P((L, d, cfg.kv_dim), (None, "fsdp", "tp_kv")),
        "wv": P((L, d, cfg.kv_dim), (None, "fsdp", "tp_kv")),
        "wo": P((L, cfg.q_dim, d), (None, "tp_heads", "fsdp")),
    }
    if cfg.qk_norm:
        t["q_norm"] = P((L, hd), (None, None), "zeros")
        t["k_norm"] = P((L, hd), (None, None), "zeros")
    return t


def _mlp_template(cfg: ArchConfig, L: int) -> Dict[str, P]:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "ln2": P((L, d), (None, None), "zeros"),
        "w_gate": P((L, d, f), (None, "fsdp", "tp_ff")),
        "w_up": P((L, d, f), (None, "fsdp", "tp_ff")),
        "w_down": P((L, f, d), (None, "tp_ff", "fsdp")),
    }


def param_template(cfg: ArchConfig):
    L = cfg.num_layers
    t = {
        "embed": P((cfg.vocab_size, cfg.d_model), ("tp_vocab", "fsdp"),
                   "embed"),
        "final_ln": P((cfg.d_model,), (None,), "zeros"),
        "layers": {**_attn_template(cfg, L), **_mlp_template(cfg, L)},
    }
    if not cfg.tie_embeddings:
        t["unembed"] = P((cfg.d_model, cfg.vocab_size), ("fsdp", "tp_vocab"))
    return t


# ---------------------------------------------------------------------------
# Layer pieces (shared with moe.py / encdec.py)
# ---------------------------------------------------------------------------


def attn(cfg: ArchConfig, lp, x, *, window: int = 0, q_offset: int = 0,
         positions=None):
    """Self-attention over a full sequence (train / prefill).

    Returns (output, (k, v)) so callers can populate a KV cache.
    """
    b, s, d = x.shape
    hd = cfg.resolved_head_dim
    xn = cm.rms_norm(x, lp["ln1"], cfg.norm_eps)
    q = (xn @ lp["wq"]).reshape(b, s, cfg.num_heads, hd)
    k = (xn @ lp["wk"]).reshape(b, s, cfg.num_kv_heads, hd)
    v = (xn @ lp["wv"]).reshape(b, s, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = cm.rms_norm(q, lp["q_norm"], cfg.norm_eps)
        k = cm.rms_norm(k, lp["k_norm"], cfg.norm_eps)
    if positions is None:
        positions = jnp.arange(s)[None, :] + q_offset
    q = cm.apply_rope(q, positions, cfg.rope_theta)
    k = cm.apply_rope(k, positions, cfg.rope_theta)
    q = hint(q, "batch", "seq", "heads", None)
    k = hint(k, "batch", "seq", "kv_heads", None)
    from repro.sharding_hints import get_rule
    out = cm.attention_chunked(q, k, v, causal=True, window=window,
                               save_memory=bool(get_rule("attn_ckpt")))
    out = out.reshape(b, s, cfg.q_dim)
    return hint(out @ lp["wo"], "batch", "seq", "embed"), (k, v)


def attn_decode(cfg: ArchConfig, lp, x, ck, cv, pos, *, window: int = 0):
    """One-token attention against a ring cache.  x: (B, 1, d);
    caches: (B, KV, S, D) — the batch-major 'bksd' layout keeps the two
    decode dots transpose-free (§Perf hillclimb 3, iteration 3)."""
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    cache_size = ck.shape[2]
    xn = cm.rms_norm(x, lp["ln1"], cfg.norm_eps)
    q = (xn @ lp["wq"]).reshape(b, 1, cfg.num_heads, hd)
    k = (xn @ lp["wk"]).reshape(b, 1, cfg.num_kv_heads, hd)
    v = (xn @ lp["wv"]).reshape(b, 1, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = cm.rms_norm(q, lp["q_norm"], cfg.norm_eps)
        k = cm.rms_norm(k, lp["k_norm"], cfg.norm_eps)
    posv = jnp.full((b, 1), pos, jnp.int32)
    q = cm.apply_rope(q, posv, cfg.rope_theta)
    k = cm.apply_rope(k, posv, cfg.rope_theta)
    # (B, 1, KV, D) -> (B, KV, 1, D) to write along the bksd seq axis
    ck, cv = cm.cache_write(ck, cv, k.transpose(0, 2, 1, 3),
                            v.transpose(0, 2, 1, 3), pos)
    valid = cm.cache_valid_len(pos, cache_size)
    out = cm.attention_decode(q, ck, cv, valid, layout="bksd")
    out = out.reshape(b, 1, cfg.q_dim)
    return out @ lp["wo"], ck, cv


def attn_decode_batch(cfg: ArchConfig, lp, x, ck, cv, pos, *,
                      window: int = 0, backend=None, cks=None, cvs=None,
                      page_table=None, layer=None):
    """Lane-major ragged decode attention: x (B, 1, d); caches
    (B, KV, S, D); pos (B,) per-lane absolute positions.

    The batched analogue of :func:`attn_decode` — one QKV projection and
    ONE fused attention call across all lanes (ragged valid vector)
    instead of vmapping B=1 steps.  ``backend`` selects the registry
    implementation ('ref' | 'pallas' | None=auto).

    With ``cks``/``cvs`` (per-slot scale buffers, (B, KV, S)) the cache
    is int8: the new token is quantized on write and attention resolves
    the q8 backend twins (in-kernel dequant).  Returns
    ``(out, ck, cv)`` in float mode, ``(out, ck, cv, cks, cvs)`` in q8
    mode.

    With ``page_table`` ((B, W) int32) the caches are global page POOLS
    — (P, KV, ps, D) payloads, (P, KV, ps) scales — and both the write
    and the attention indirect through the lane's table row (paged
    backend twins); logical capacity becomes W * ps per lane.  With
    ``layer`` as well the pools are every layer's, stacked (L, P, KV, ps,
    D), and are written and read at that layer in place."""
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    paged = page_table is not None
    if paged:
        cache_size = page_table.shape[1] * ck.shape[-2]  # W * ps logical
    else:
        cache_size = ck.shape[2]
    with jax.named_scope("qkv"):
        xn = cm.rms_norm(x, lp["ln1"], cfg.norm_eps)
        q = (xn @ lp["wq"]).reshape(b, 1, cfg.num_heads, hd)
        k = (xn @ lp["wk"]).reshape(b, 1, cfg.num_kv_heads, hd)
        v = (xn @ lp["wv"]).reshape(b, 1, cfg.num_kv_heads, hd)
        if cfg.qk_norm:
            q = cm.rms_norm(q, lp["q_norm"], cfg.norm_eps)
            k = cm.rms_norm(k, lp["k_norm"], cfg.norm_eps)
        posv = pos[:, None]                            # (B, 1) per-lane
        q = cm.apply_rope(q, posv, cfg.rope_theta)
        k = cm.apply_rope(k, posv, cfg.rope_theta)
        kT, vT = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    valid = cm.cache_valid_len(pos, cache_size)        # (B,) ragged
    if cks is None:
        with jax.named_scope("kv_write"):
            if paged:
                ck, cv = cm.cache_write_batch_paged(ck, cv, page_table, kT,
                                                    vT, pos, layer)
            else:
                ck, cv = cm.cache_write_batch(ck, cv, kT, vT, pos)
        with jax.named_scope("attention"):
            out = cm.decode_attention_named(q, ck, cv, valid,
                                            backend=backend,
                                            page_table=page_table,
                                            layer=layer)
            out = out.reshape(b, 1, cfg.q_dim) @ lp["wo"]
        return out, ck, cv
    with jax.named_scope("kv_write"):
        if paged:
            ck, cv, cks, cvs = cm.cache_write_batch_paged_q8(
                ck, cv, cks, cvs, page_table, kT, vT, pos, layer)
        else:
            ck, cv, cks, cvs = cm.cache_write_batch_q8(ck, cv, cks, cvs, kT,
                                                       vT, pos)
    with jax.named_scope("attention"):
        out = cm.decode_attention_named(q, ck, cv, valid, backend=backend,
                                        k_scale=cks, v_scale=cvs,
                                        page_table=page_table, layer=layer)
        out = out.reshape(b, 1, cfg.q_dim) @ lp["wo"]
    return out, ck, cv, cks, cvs


def mlp(cfg: ArchConfig, lp, x):
    xn = cm.rms_norm(x, lp["ln2"], cfg.norm_eps)
    return cm.swiglu(xn, lp["w_gate"], lp["w_up"], lp["w_down"])


def _logits(cfg: ArchConfig, params, x):
    x = cm.rms_norm(x, params["final_ln"], cfg.norm_eps)
    if cfg.tie_embeddings:
        w = params["embed"].T
    else:
        w = params["unembed"]
    return hint((x @ w.astype(x.dtype)), "batch", "seq", "vocab_act")


def _embed(cfg: ArchConfig, params, tokens):
    x = params["embed"][tokens]
    return hint(x, "batch", "seq", "embed")


# ---------------------------------------------------------------------------
# Forward / decode
# ---------------------------------------------------------------------------


def forward(cfg: ArchConfig, params, tokens, *, window: int = 0,
            remat: bool = True):
    """tokens (B, S) -> logits (B, S, V)."""
    x = _embed(cfg, params, tokens)

    def layer(x, lp):
        a, _ = attn(cfg, lp, x, window=window)
        x = x + a
        x = x + mlp(cfg, lp, x)
        return x, None

    body = jax.checkpoint(layer) if remat else layer
    x, _ = lax.scan(body, x, params["layers"])
    return _logits(cfg, params, x)


def loss_fn(cfg: ArchConfig, params, batch, *, window: int = 0):
    logits = forward(cfg, params, batch["tokens"], window=window)
    loss = cm.softmax_xent(logits[:, :-1], batch["labels"][:, 1:])
    return loss, {"loss": loss}


def kv_cache_dtype(dtype, kv_dtype):
    """Resolve the K/V buffer dtype from a ``kv_dtype`` option: ``None``
    keeps the cache dtype (back-compat), 'bf16' halves KV bytes, 'int8'
    quarters them (plus per-slot fp32 scales)."""
    if kv_dtype is None:
        return dtype
    try:
        return {"bf16": jnp.bfloat16, "int8": jnp.int8}[kv_dtype]
    except KeyError:
        raise ValueError(f"unknown kv_dtype {kv_dtype!r} "
                         "(expected None, 'bf16' or 'int8')") from None


def init_cache(cfg: ArchConfig, batch: int, cache_len: int,
               dtype=jnp.bfloat16, kv_dtype=None, page_size=None,
               num_pages=None):
    """Decoder-only cache layout: (L, B, KV, S, D) ('bksd').

    ``kv_dtype='int8'`` stores K/V as int8 plus per-(lane, head, slot)
    fp32 scale buffers — the layout the ``*_q8`` decode backends consume.

    ``page_size`` switches to the PAGED layout: instead of per-lane ring
    buffers, K/V live in global pools of ``num_pages`` fixed-size pages
    — ``k_pages``/``v_pages`` (L, P, KV, ps, D) plus a shared int32
    ``page_table`` (B, W) mapping each lane's logical KV block to a
    physical page (W = ceil(cache_len / ps)).  Page 0 is the reserved
    garbage page (never allocated; inactive lanes' zeroed table rows
    land there).  int8 adds (L, P, KV, ps) scale pools.
    """
    L, kv, hd = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
    kvd = kv_cache_dtype(dtype, kv_dtype)
    if page_size is None:
        cache = {
            "k": jnp.zeros((L, batch, kv, cache_len, hd), kvd),
            "v": jnp.zeros((L, batch, kv, cache_len, hd), kvd),
        }
        if kv_dtype == "int8":
            cache["k_scale"] = jnp.zeros((L, batch, kv, cache_len),
                                         jnp.float32)
            cache["v_scale"] = jnp.zeros((L, batch, kv, cache_len),
                                         jnp.float32)
        return cache
    ps = page_size
    w = -(-cache_len // ps)
    p = num_pages if num_pages is not None else 1 + batch * w
    cache = {
        "k_pages": jnp.zeros((L, p, kv, ps, hd), kvd),
        "v_pages": jnp.zeros((L, p, kv, ps, hd), kvd),
        "page_table": jnp.zeros((batch, w), jnp.int32),
    }
    if kv_dtype == "int8":
        cache["k_scale_pages"] = jnp.zeros((L, p, kv, ps), jnp.float32)
        cache["v_scale_pages"] = jnp.zeros((L, p, kv, ps), jnp.float32)
    return cache


def paged_info(cfg: ArchConfig, cache_len: int, page_size: int):
    """Paging capabilities of this family: incremental page allocation
    (pages are claimed as the sequence grows) and prompt-prefix sharing
    are both supported.  Logical capacity rounds cache_len up to whole
    pages."""
    w = -(-cache_len // page_size)
    return {"pages_per_lane": w, "capacity": w * page_size,
            "alloc": "incremental", "prefix_sharing": True}


def cache_splice_paged(cfg: ArchConfig, cache, row, slot, pages,
                       page_size: int):
    """Splice a prefilled B=1 ring cache ``row`` into lane ``slot`` of a
    paged ``cache``, scattering the first ``len(pages)`` KV blocks into
    the given physical pages and rewriting the lane's table row.

    ``pages`` is a static-length int32 vector (page COUNT is a compile-
    time constant — one jit specialization per prefill bucket, same
    policy as the scheduler's static plen); page IDs stay traced."""
    n = pages.shape[0]
    ps = page_size
    w = cache["page_table"].shape[1]
    out = dict(cache)
    for key in ("k", "v"):
        src = row[key][:, 0, :, :n * ps]               # (L, KV, n*ps, D)
        L, kv = src.shape[0], src.shape[1]
        x = src.reshape(L, kv, n, ps, -1).transpose(0, 2, 1, 3, 4)
        pool = cache[key + "_pages"]
        out[key + "_pages"] = pool.at[:, pages].set(x.astype(pool.dtype))
        skey = key + "_scale"
        if skey in row:
            ssrc = row[skey][:, 0, :, :n * ps]         # (L, KV, n*ps)
            sx = ssrc.reshape(L, kv, n, ps).transpose(0, 2, 1, 3)
            spool = cache[skey + "_pages"]
            out[skey + "_pages"] = spool.at[:, pages].set(sx)
    trow = jnp.zeros((w,), jnp.int32).at[:n].set(pages.astype(jnp.int32))
    out["page_table"] = cache["page_table"].at[slot].set(trow)
    return out


def cache_to_kv_dtype(cfg: ArchConfig, cache, kv_dtype):
    """Convert a float prefill cache into the ``kv_dtype`` layout of
    :func:`init_cache` (same tree structure, so a scheduler can splice
    an admitted lane into its live state).  'int8' quantizes each ring
    slot over head_dim — one scale per (layer, lane, head, slot)."""
    if kv_dtype is None:
        return cache
    if kv_dtype == "bf16":
        return {**cache, "k": cache["k"].astype(jnp.bfloat16),
                "v": cache["v"].astype(jnp.bfloat16)}
    assert kv_dtype == "int8", kv_dtype
    from repro.core.quantize import quantize_into
    kq, ks = quantize_into(cache["k"], axis=-1)
    vq, vs = quantize_into(cache["v"], axis=-1)
    return {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}


def cache_spec(cfg: ArchConfig, batch: int, cache_len: int, dtype):
    """ShapeDtypeStruct + logical axes for the dry-run."""
    L, kv, hd = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
    shape = (L, batch, kv, cache_len, hd)
    axes = (None, "batch", "tp_kv", "cache_seq", None)
    return ({"k": jax.ShapeDtypeStruct(shape, dtype),
             "v": jax.ShapeDtypeStruct(shape, dtype)},
            {"k": axes, "v": axes})


def decode_step(cfg: ArchConfig, params, token, cache, pos, *,
                window: int = 0):
    """token (B, 1) int32; pos scalar int32.  Returns (logits, cache).

    The cache streams through the layer scan as xs/ys — XLA streams the
    per-layer slices; carrying the whole buffer instead provokes
    conservative full-cache copies (§Perf h3 it2, REFUTED, 3x worse).
    """
    x = _embed(cfg, params, token)

    def layer(x, scanned):
        lp, ck, cv = scanned
        a, ck, cv = attn_decode(cfg, lp, x, ck, cv, pos, window=window)
        x = x + a
        x = x + mlp(cfg, lp, x)
        return x, (ck, cv)

    x, (ck, cv) = lax.scan(layer, x, (params["layers"], cache["k"],
                                      cache["v"]))
    return _logits(cfg, params, x), {"k": ck, "v": cv}


def decode_step_batch(cfg: ArchConfig, params, tokens, cache, pos, *,
                      window: int = 0, attn_backend=None):
    """Lane-major decode: tokens (B, 1) int32; pos (B,) int32 per-lane.

    The continuous-batching hot path: batched QKV projections, per-lane
    RoPE positions and ring writes, and one fused ragged attention call
    per layer — instead of vmapping B=1 :func:`decode_step` over lanes.
    Returns (logits (B, 1, V), cache), numerically matching the vmapped
    reference path.  An int8 cache (the ``k_scale`` leaf marks it) takes
    the quantizing write + q8 attention path; the branch is static, so
    each cache dtype compiles its own specialization.

    A paged cache (the ``page_table`` leaf marks it) is carried through
    the layer scan whole: the stacked page pools are written and read in
    place at each layer's index (:func:`decode_layers_paged`).  The
    scheduler's step donates the state it passes in, so the returned
    pools are the same device buffers as the given ones, and a caller
    may keep no leaf of a state it has passed to a step."""
    with jax.named_scope("embed"):
        x = _embed(cfg, params, tokens)
    if "page_table" in cache:
        return _decode_step_batch_paged(cfg, params, x, cache, pos,
                                        window=window,
                                        attn_backend=attn_backend)
    quantized = "k_scale" in cache

    if quantized:
        def layer(x, scanned):
            lp, ck, cv, cks, cvs = scanned
            a, ck, cv, cks, cvs = attn_decode_batch(
                cfg, lp, x, ck, cv, pos, window=window,
                backend=attn_backend, cks=cks, cvs=cvs)
            x = x + a
            x = x + mlp(cfg, lp, x)
            return x, (ck, cv, cks, cvs)

        x, (ck, cv, cks, cvs) = lax.scan(
            layer, x, (params["layers"], cache["k"], cache["v"],
                       cache["k_scale"], cache["v_scale"]))
        return _logits(cfg, params, x), {"k": ck, "v": cv,
                                         "k_scale": cks, "v_scale": cvs}

    def layer(x, scanned):
        lp, ck, cv = scanned
        a, ck, cv = attn_decode_batch(cfg, lp, x, ck, cv, pos,
                                      window=window, backend=attn_backend)
        x = x + a
        x = x + mlp(cfg, lp, x)
        return x, (ck, cv)

    x, (ck, cv) = lax.scan(layer, x, (params["layers"], cache["k"],
                                      cache["v"]))
    return _logits(cfg, params, x), {"k": ck, "v": cv}


PAGE_POOLS = ("k_pages", "v_pages", "k_scale_pages", "v_scale_pages")


def decode_layers_paged(cfg: ArchConfig, layers, x, cache, pos, ffn, *,
                        window: int = 0, attn_backend=None):
    """The layer scan of a paged decode step, for every family whose
    layer is attention then a feed-forward block.

    The scan carries the whole stacked pools (``PAGE_POOLS``: the
    payloads and, int8, the scale pools) and runs over ``layers`` and
    the layer's index: each layer writes its token into the pools and
    reads them at that index, in place, so no layer slice is copied out
    or written back.  ``ffn(lp, x)`` returns the layer's output and a
    per-layer value, stacked by the scan.  Returns (x, cache, stacked
    values); the (B, W) page table is shared by every layer and comes
    back unchanged."""
    pt = cache["page_table"]
    names = tuple(n for n in PAGE_POOLS if n in cache)
    n_layers = jax.tree.leaves(layers)[0].shape[0]

    def layer(carry, scanned):
        lp, index = scanned
        x, (ck, cv, *scales) = carry
        kw = dict(cks=scales[0], cvs=scales[1]) if scales else {}
        a, *pools = attn_decode_batch(cfg, lp, x, ck, cv, pos,
                                      window=window, backend=attn_backend,
                                      page_table=pt, layer=index, **kw)
        x, y = ffn(lp, x + a)
        return (x, tuple(pools)), y

    (x, pools), ys = lax.scan(
        layer, (x, tuple(cache[n] for n in names)),
        (layers, jnp.arange(n_layers, dtype=jnp.int32)))
    return x, {**cache, **dict(zip(names, pools))}, ys


def _decode_step_batch_paged(cfg: ArchConfig, params, x, cache, pos, *,
                             window: int = 0, attn_backend=None):
    """Paged :func:`decode_step_batch`: one scan body for bf16 and int8
    pools, which :func:`decode_layers_paged` carries whole and addresses
    by layer index."""
    def ffn(lp, x):
        with jax.named_scope("mlp"):
            return x + mlp(cfg, lp, x), None

    x, cache, _ = decode_layers_paged(cfg, params["layers"], x, cache, pos,
                                      ffn, window=window,
                                      attn_backend=attn_backend)
    with jax.named_scope("head"):
        logits = _logits(cfg, params, x)
    return logits, cache


def prefill(cfg: ArchConfig, params, tokens, cache_len: int,
            *, window: int = 0, cache_dtype=jnp.bfloat16):
    """Run the full prompt, returning logits and a populated cache."""
    b, s = tokens.shape
    x = _embed(cfg, params, tokens)

    def layer(x, lp):
        a, (k, v) = attn(cfg, lp, x, window=window)
        x = x + a
        x = x + mlp(cfg, lp, x)
        return x, (k.astype(cache_dtype), v.astype(cache_dtype))

    x, (ks, vs) = lax.scan(layer, x, params["layers"])
    cache = init_cache(cfg, b, cache_len, cache_dtype)
    keep = min(s, cache_len)
    # (L, B, S, KV, D) stacked attn outputs -> bksd (L, B, KV, S, D)
    ks = ks.transpose(0, 1, 3, 2, 4)
    vs = vs.transpose(0, 1, 3, 2, 4)
    ck = lax.dynamic_update_slice_in_dim(
        cache["k"], ks[:, :, :, s - keep:], 0, axis=3)
    cv = lax.dynamic_update_slice_in_dim(
        cache["v"], vs[:, :, :, s - keep:], 0, axis=3)
    if s > cache_len:
        # ring alignment: token t lives at slot t % cache_len
        ck = jnp.roll(ck, s % cache_len, axis=3)
        cv = jnp.roll(cv, s % cache_len, axis=3)
    return _logits(cfg, params, x), {"k": ck, "v": cv}
