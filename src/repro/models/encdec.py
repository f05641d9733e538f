"""Whisper-style encoder-decoder transformer backbone (audio family).

[arXiv:2212.04356]  The mel-spectrogram + conv feature extractor is the
assignment's allowed stub: the model consumes precomputed frame embeddings
(B, encoder_seq, d_model).  Encoder: bidirectional self-attention with
sinusoidal positions, LayerNorm + GELU MLP (as in Whisper).  Decoder:
causal self-attention (RoPE — a deliberate deviation from Whisper's learned
448-position table so the 32k/500k decode shapes are reachable; recorded in
DESIGN.md) + cross-attention to the encoder output + GELU MLP.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig
from repro.models import common as cm
from repro.models import transformer as tfm
from repro.models.common import P
from repro.sharding_hints import hint


def _ln(x, lp, name, eps=1e-5):
    return cm.layer_norm(x, lp[f"{name}_w"], lp[f"{name}_b"], eps)


def _attn_t(cfg, L, prefix=""):
    d = cfg.d_model
    return {
        f"{prefix}ln_w": P((L, d), (None, None), "ones"),
        f"{prefix}ln_b": P((L, d), (None, None), "zeros"),
        f"{prefix}wq": P((L, d, cfg.q_dim), (None, "fsdp", "tp_heads")),
        f"{prefix}bq": P((L, cfg.q_dim), (None, "tp_heads"), "zeros"),
        f"{prefix}wk": P((L, d, cfg.kv_dim), (None, "fsdp", "tp_kv")),
        f"{prefix}wv": P((L, d, cfg.kv_dim), (None, "fsdp", "tp_kv")),
        f"{prefix}bv": P((L, cfg.kv_dim), (None, "tp_kv"), "zeros"),
        f"{prefix}wo": P((L, cfg.q_dim, d), (None, "tp_heads", "fsdp")),
        f"{prefix}bo": P((L, d), (None, "fsdp"), "zeros"),
    }


def _mlp_t(cfg, L):
    d, f = cfg.d_model, cfg.d_ff
    return {
        "mlp_ln_w": P((L, d), (None, None), "ones"),
        "mlp_ln_b": P((L, d), (None, None), "zeros"),
        "w_in": P((L, d, f), (None, "fsdp", "tp_ff")),
        "b_in": P((L, f), (None, "tp_ff"), "zeros"),
        "w_out": P((L, f, d), (None, "tp_ff", "fsdp")),
        "b_out": P((L, d), (None, "fsdp"), "zeros"),
    }


def param_template(cfg: ArchConfig):
    d = cfg.d_model
    return {
        "embed": P((cfg.vocab_size, d), ("tp_vocab", "fsdp"), "embed"),
        "enc_final_ln_w": P((d,), (None,), "ones"),
        "enc_final_ln_b": P((d,), (None,), "zeros"),
        "final_ln_w": P((d,), (None,), "ones"),
        "final_ln_b": P((d,), (None,), "zeros"),
        "enc": {**_attn_t(cfg, cfg.encoder_layers), **_mlp_t(cfg, cfg.encoder_layers)},
        "dec": {**_attn_t(cfg, cfg.num_layers),
                **_attn_t(cfg, cfg.num_layers, prefix="x_"),
                **_mlp_t(cfg, cfg.num_layers)},
    }


def sinusoid(seq: int, d: int):
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, 2 * dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _qkv(cfg, lp, xq, xkv, prefix=""):
    b, sq = xq.shape[:2]
    skv = xkv.shape[1]
    hd = cfg.resolved_head_dim
    q = (xq @ lp[f"{prefix}wq"] + lp[f"{prefix}bq"]).reshape(
        b, sq, cfg.num_heads, hd)
    k = (xkv @ lp[f"{prefix}wk"]).reshape(b, skv, cfg.num_kv_heads, hd)
    v = (xkv @ lp[f"{prefix}wv"] + lp[f"{prefix}bv"]).reshape(
        b, skv, cfg.num_kv_heads, hd)
    return q, k, v


def _mlp(cfg, lp, x):
    xn = _ln(x, lp, "mlp_ln")
    h = hint(jax.nn.gelu(xn @ lp["w_in"] + lp["b_in"]), "batch", "seq", "ff")
    return hint(h @ lp["w_out"] + lp["b_out"], "batch", "seq", "embed")


def encode(cfg: ArchConfig, params, frames):
    """frames: (B, S_enc, d) stubbed conv-frontend output -> (B, S_enc, d)."""
    x = frames + sinusoid(frames.shape[1], cfg.d_model).astype(frames.dtype)

    def layer(x, lp):
        xn = _ln(x, lp, "ln")
        q, k, v = _qkv(cfg, lp, xn, xn)
        a = cm.attention_chunked(q, k, v, causal=False)
        x = x + (a.reshape(*x.shape[:2], cfg.q_dim) @ lp["wo"] + lp["bo"])
        x = x + _mlp(cfg, lp, x)
        return x, None

    x, _ = lax.scan(layer, x, params["enc"])
    return cm.layer_norm(x, params["enc_final_ln_w"], params["enc_final_ln_b"])


def _dec_layer(cfg, lp, x, enc_out, *, window=0):
    """Returns (x, (self_k, self_v, cross_k, cross_v))."""
    b, s = x.shape[:2]
    hd = cfg.resolved_head_dim
    xn = _ln(x, lp, "ln")
    q, k, v = _qkv(cfg, lp, xn, xn)
    pos = jnp.arange(s)[None]
    q = cm.apply_rope(q, pos, cfg.rope_theta)
    k = cm.apply_rope(k, pos, cfg.rope_theta)
    a = cm.attention_chunked(q, k, v, causal=True, window=window)
    x = x + (a.reshape(b, s, cfg.q_dim) @ lp["wo"] + lp["bo"])
    xn = _ln(x, lp, "x_ln")
    qx, kx, vx = _qkv(cfg, lp, xn, enc_out, prefix="x_")
    ax = cm.attention_chunked(qx, kx, vx, causal=False)
    x = x + (ax.reshape(b, s, cfg.q_dim) @ lp["x_wo"] + lp["x_bo"])
    x = x + _mlp(cfg, lp, x)
    return x, (k, v, kx, vx)


def forward(cfg: ArchConfig, params, tokens, frames, *, window: int = 0,
            remat: bool = True):
    enc_out = encode(cfg, params, frames)
    x = params["embed"][tokens]
    x = hint(x, "batch", "seq", "embed")

    def layer(x, lp):
        x, _ = _dec_layer(cfg, lp, x, enc_out, window=window)
        return x, None

    body = jax.checkpoint(layer) if remat else layer
    x, _ = lax.scan(body, x, params["dec"])
    x = cm.layer_norm(x, params["final_ln_w"], params["final_ln_b"])
    return hint(x @ params["embed"].T.astype(x.dtype),
                "batch", "seq", "vocab_act")


def loss_fn(cfg: ArchConfig, params, batch, *, window: int = 0):
    logits = forward(cfg, params, batch["tokens"], batch["frames"],
                     window=window)
    loss = cm.softmax_xent(logits[:, :-1], batch["labels"][:, 1:])
    return loss, {"loss": loss}


def init_cache(cfg: ArchConfig, batch: int, cache_len: int,
               dtype=jnp.bfloat16, kv_dtype=None, page_size=None,
               num_pages=None):
    L, kv, hd = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
    se = cfg.encoder_seq
    xd = jnp.bfloat16 if kv_dtype == "bf16" else dtype
    cache = {
        "xk": jnp.zeros((L, batch, se, kv, hd), xd),
        "xv": jnp.zeros((L, batch, se, kv, hd), xd),
    }
    # decoder self-attention: the decoder-only layout of
    # transformer.init_cache ((L, B, KV, S, D) ring or (L, P, KV, ps, D)
    # pages); xk/xv (cross-attention, written once at admission) stay
    # dense per-lane (L, B, Se, KV, D)
    cache.update(tfm.init_cache(cfg, batch, cache_len, dtype,
                                kv_dtype=kv_dtype, page_size=page_size,
                                num_pages=num_pages))
    return cache


def paged_info(cfg: ArchConfig, cache_len: int, page_size: int):
    """Incremental paging of the decoder self-attention ring; prefix
    sharing is OFF — the dense per-lane cross-attention caches (xk/xv)
    are lane state the prefix cache cannot share, so a 'hit' would still
    need a full encoder pass."""
    w = -(-cache_len // page_size)
    return {"pages_per_lane": w, "capacity": w * page_size,
            "alloc": "incremental", "prefix_sharing": False}


def cache_splice_paged(cfg: ArchConfig, cache, row, slot, pages,
                       page_size: int):
    """Splice a prefilled B=1 cache into lane ``slot``: dense xk/xv land
    in the lane row; the self-attention blocks scatter into the given
    pages exactly as in :func:`transformer.cache_splice_paged`."""
    out = tfm.cache_splice_paged(cfg, cache, row, slot, pages, page_size)
    out["xk"] = cache["xk"].at[:, slot].set(
        row["xk"][:, 0].astype(cache["xk"].dtype))
    out["xv"] = cache["xv"].at[:, slot].set(
        row["xv"][:, 0].astype(cache["xv"].dtype))
    return out


def cache_to_kv_dtype(cfg: ArchConfig, cache, kv_dtype):
    """Quantize only the decoder self-attention ring; the cross-attention
    caches (xk/xv — written once at admission, read every step) stay in
    the float cache dtype."""
    if kv_dtype is None:
        return cache
    if kv_dtype == "bf16":
        return {k: v.astype(jnp.bfloat16) for k, v in cache.items()}
    assert kv_dtype == "int8", kv_dtype
    from repro.core.quantize import quantize_into
    kq, ks = quantize_into(cache["k"], axis=-1)
    vq, vs = quantize_into(cache["v"], axis=-1)
    return {**cache, "k": kq, "v": vq, "k_scale": ks, "v_scale": vs}


def cache_spec(cfg: ArchConfig, batch: int, cache_len: int, dtype):
    L, kv, hd = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
    se = cfg.encoder_seq
    kvax = (None, "batch", "tp_kv", "cache_seq", None)
    return ({
        "k": jax.ShapeDtypeStruct((L, batch, kv, cache_len, hd), dtype),
        "v": jax.ShapeDtypeStruct((L, batch, kv, cache_len, hd), dtype),
        "xk": jax.ShapeDtypeStruct((L, batch, se, kv, hd), dtype),
        "xv": jax.ShapeDtypeStruct((L, batch, se, kv, hd), dtype),
    }, {"k": kvax, "v": kvax,
        "xk": (None, "batch", None, "tp_kv", None),
        "xv": (None, "batch", None, "tp_kv", None)})


def decode_step(cfg: ArchConfig, params, token, cache, pos, *,
                window: int = 0):
    x = params["embed"][token]                         # (B,1,d)
    hd = cfg.resolved_head_dim
    b = x.shape[0]

    def layer(x, scanned):
        lp, ck, cv, xk, xv = scanned
        xn = _ln(x, lp, "ln")
        q, k, v = _qkv(cfg, lp, xn, xn)
        posv = jnp.full((b, 1), pos, jnp.int32)
        q = cm.apply_rope(q, posv, cfg.rope_theta)
        k = cm.apply_rope(k, posv, cfg.rope_theta)
        # (B, 1, KV, D) -> (B, KV, 1, D) to write along the bksd seq axis
        ck, cv = cm.cache_write(ck, cv, k.transpose(0, 2, 1, 3),
                                v.transpose(0, 2, 1, 3), pos)
        valid = cm.cache_valid_len(pos, ck.shape[2])
        a = cm.attention_decode(q, ck, cv, valid, layout="bksd")
        x = x + (a.reshape(b, 1, cfg.q_dim) @ lp["wo"] + lp["bo"])
        xn = _ln(x, lp, "x_ln")
        qx = (xn @ lp["x_wq"] + lp["x_bq"]).reshape(b, 1, cfg.num_heads, hd)
        ax = cm.attention_decode(qx, xk, xv, xk.shape[1])
        x = x + (ax.reshape(b, 1, cfg.q_dim) @ lp["x_wo"] + lp["x_bo"])
        x = x + _mlp(cfg, lp, x)
        return x, (ck, cv)

    x, (ck, cv) = lax.scan(
        layer, x, (params["dec"], cache["k"], cache["v"], cache["xk"],
                   cache["xv"]))
    x = cm.layer_norm(x, params["final_ln_w"], params["final_ln_b"])
    logits = x @ params["embed"].T.astype(x.dtype)
    return logits, {"k": ck, "v": cv, "xk": cache["xk"], "xv": cache["xv"]}


def decode_step_batch(cfg: ArchConfig, params, token, cache, pos, *,
                      window: int = 0, attn_backend=None):
    """Lane-major decode: token (B, 1); pos (B,) per-lane positions.
    Self-attention goes through the ragged named-backend decode path
    (per-lane RoPE + ring writes, bksd cache layout); cross-attention
    keys are the full encoder output, identical for every lane.  A paged
    cache (``page_table`` leaf) pages only the self-attention ring —
    xk/xv stay dense per-lane."""
    x = params["embed"][token]                         # (B,1,d)
    hd = cfg.resolved_head_dim
    b = x.shape[0]
    paged = "page_table" in cache
    pt = cache.get("page_table")
    kk, vk = ("k_pages", "v_pages") if paged else ("k", "v")
    ksk, vsk = ("k_scale_pages", "v_scale_pages") if paged \
        else ("k_scale", "v_scale")
    quantized = ksk in cache
    if paged:
        cap = pt.shape[1] * cache[kk].shape[3]         # W * ps logical
    else:
        cap = cache[kk].shape[3]

    def self_attn(lp, x, ck, cv, cks=None, cvs=None):
        xn = _ln(x, lp, "ln")
        q, k, v = _qkv(cfg, lp, xn, xn)
        posv = pos[:, None]
        q = cm.apply_rope(q, posv, cfg.rope_theta)
        k = cm.apply_rope(k, posv, cfg.rope_theta)
        kT, vT = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
        valid = cm.cache_valid_len(pos, cap)
        if cks is None:
            if paged:
                ck, cv = cm.cache_write_batch_paged(ck, cv, pt, kT, vT, pos)
            else:
                ck, cv = cm.cache_write_batch(ck, cv, kT, vT, pos)
            a = cm.decode_attention_named(q, ck, cv, valid,
                                          backend=attn_backend,
                                          page_table=pt)
        else:
            if paged:
                ck, cv, cks, cvs = cm.cache_write_batch_paged_q8(
                    ck, cv, cks, cvs, pt, kT, vT, pos)
            else:
                ck, cv, cks, cvs = cm.cache_write_batch_q8(
                    ck, cv, cks, cvs, kT, vT, pos)
            a = cm.decode_attention_named(q, ck, cv, valid,
                                          backend=attn_backend,
                                          k_scale=cks, v_scale=cvs,
                                          page_table=pt)
        x = x + (a.reshape(b, 1, cfg.q_dim) @ lp["wo"] + lp["bo"])
        return x, ck, cv, cks, cvs

    def rest(lp, x, xk, xv):
        xn = _ln(x, lp, "x_ln")
        qx = (xn @ lp["x_wq"] + lp["x_bq"]).reshape(b, 1, cfg.num_heads, hd)
        ax = cm.attention_decode(qx, xk, xv, xk.shape[1])
        x = x + (ax.reshape(b, 1, cfg.q_dim) @ lp["x_wo"] + lp["x_bo"])
        return x + _mlp(cfg, lp, x)

    if quantized:
        def layer(x, scanned):
            lp, ck, cv, cks, cvs, xk, xv = scanned
            x, ck, cv, cks, cvs = self_attn(lp, x, ck, cv, cks, cvs)
            return rest(lp, x, xk, xv), (ck, cv, cks, cvs)

        x, (ck, cv, cks, cvs) = lax.scan(
            layer, x, (params["dec"], cache[kk], cache[vk],
                       cache[ksk], cache[vsk], cache["xk"],
                       cache["xv"]))
        new_cache = {kk: ck, vk: cv, ksk: cks, vsk: cvs,
                     "xk": cache["xk"], "xv": cache["xv"]}
    else:
        def layer(x, scanned):
            lp, ck, cv, xk, xv = scanned
            x, ck, cv, _, _ = self_attn(lp, x, ck, cv)
            return rest(lp, x, xk, xv), (ck, cv)

        x, (ck, cv) = lax.scan(
            layer, x, (params["dec"], cache[kk], cache[vk], cache["xk"],
                       cache["xv"]))
        new_cache = {kk: ck, vk: cv, "xk": cache["xk"], "xv": cache["xv"]}
    if paged:
        new_cache["page_table"] = pt
    x = cm.layer_norm(x, params["final_ln_w"], params["final_ln_b"])
    logits = x @ params["embed"].T.astype(x.dtype)
    return logits, new_cache


def prefill(cfg: ArchConfig, params, tokens, cache_len: int, frames=None, *,
            window: int = 0, cache_dtype=jnp.bfloat16):
    b, s = tokens.shape
    if frames is None:
        # match the compute dtype or the encoder scan carry flips types
        # (f32 serving params + bf16 frames broke the decode-only path)
        frames = jnp.zeros((b, cfg.encoder_seq, cfg.d_model),
                           params["embed"].dtype)
    enc_out = encode(cfg, params, frames)
    x = params["embed"][tokens]

    def layer(x, lp):
        x, (k, v, kx, vx) = _dec_layer(cfg, lp, x, enc_out, window=window)
        return x, tuple(t.astype(cache_dtype) for t in (k, v, kx, vx))

    x, (ks, vs, kxs, vxs) = lax.scan(layer, x, params["dec"])
    x = cm.layer_norm(x, params["final_ln_w"], params["final_ln_b"])
    logits = x @ params["embed"].T.astype(x.dtype)
    cache = init_cache(cfg, b, cache_len, cache_dtype)
    keep = min(s, cache_len)
    # (L, B, S, KV, D) stacked attn outputs -> bksd (L, B, KV, S, D)
    ks = ks.transpose(0, 1, 3, 2, 4)
    vs = vs.transpose(0, 1, 3, 2, 4)
    ck = lax.dynamic_update_slice_in_dim(cache["k"], ks[:, :, :, s - keep:],
                                         0, axis=3)
    cv = lax.dynamic_update_slice_in_dim(cache["v"], vs[:, :, :, s - keep:],
                                         0, axis=3)
    if s > cache_len:
        ck = jnp.roll(ck, s % cache_len, axis=3)
        cv = jnp.roll(cv, s % cache_len, axis=3)
    return logits, {"k": ck, "v": cv, "xk": kxs, "xv": vxs}
