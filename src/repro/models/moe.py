"""Mixture-of-Experts decoder (qwen3-moe, granite-moe families).

Token-choice top-k routing with sort-based capacity dispatch: tokens are
argsorted by expert id into an (E, C, d) buffer, each expert runs a dense
SwiGLU over its slice, and results are combined with the (renormalized)
router weights.  Overflowing tokens beyond capacity C are dropped (classic
GShard/Switch semantics, capacity_factor controls the slack).

Sharding: the expert dim carries the logical axis ``experts`` -> the mesh
``model`` axis when E divides it (expert parallelism; the (T,d)->(E,C,d)
gather lowers to an all-to-all under GSPMD).  For banks like granite's 40
experts that don't divide the 16-way axis, the divisibility fallback in
``sharding_hints`` replicates the expert dim and shards the per-expert
``tp_ff`` dim instead.
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


def param_template(cfg: ArchConfig):
    L, d, f, E = cfg.num_layers, cfg.d_model, cfg.d_ff, cfg.num_experts
    t = {
        "embed": P((cfg.vocab_size, d), ("tp_vocab", "fsdp"), "embed"),
        "final_ln": P((d,), (None,), "zeros"),
        "layers": {
            **tfm._attn_template(cfg, L),
            "ln2": P((L, d), (None, None), "zeros"),
            "router": P((L, d, E), (None, "fsdp", None)),
            "we_gate": P((L, E, d, f), (None, "experts", "fsdp", "tp_ff")),
            "we_up": P((L, E, d, f), (None, "experts", "fsdp", "tp_ff")),
            "we_down": P((L, E, f, d), (None, "experts", "tp_ff", "fsdp")),
        },
    }
    if not cfg.tie_embeddings:
        t["unembed"] = P((d, cfg.vocab_size), ("fsdp", "tp_vocab"))
    return t


def _capacity(cfg: ArchConfig, num_tokens: int) -> int:
    c = int(math.ceil(cfg.capacity_factor * num_tokens *
                      cfg.experts_per_token / cfg.num_experts))
    return max(8, min(c, num_tokens))  # pad to a sane floor, cap at T


def _route(cfg: ArchConfig, xf, router):
    """(T, d) tokens -> (top_p, top_e, aux) router outputs."""
    E, k = cfg.num_experts, cfg.experts_per_token
    T = xf.shape[0]
    logits = (xf.astype(jnp.float32) @ router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                      # (T, E)
    top_p, top_e = lax.top_k(probs, k)                           # (T, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance aux loss
    me = probs.mean(axis=0)
    one_hot = jax.nn.one_hot(top_e, E, dtype=jnp.float32)
    ce = one_hot.sum(axis=(0, 1)) / (T * k)
    aux = E * jnp.sum(me * ce)
    return top_p, top_e, aux


def _dispatch(xf, top_e, top_p, E: int, C: int):
    """Sort-based capacity dispatch: (T,d) -> (E,C,d) + combine metadata."""
    T, d = xf.shape
    k = top_e.shape[-1]
    flat_e = top_e.reshape(-1)                                   # (T*k,)
    flat_w = top_p.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), k)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    counts = jnp.bincount(flat_e, length=E)
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(T * k) - starts[se]
    ok = pos_in_e < C
    dest = jnp.where(ok, se * C + pos_in_e, E * C)               # drop slot
    xbuf = jnp.zeros((E * C + 1, d), xf.dtype).at[dest].set(xf[st])
    return xbuf[:-1].reshape(E, C, d), (dest, ok, st, sw)


def _combine(y_flat, meta, T: int, dtype):
    """(E*C, d) expert outputs -> (T, d) weighted combine."""
    dest, ok, st, sw = meta
    n = y_flat.shape[0]
    gathered = jnp.take(y_flat, jnp.minimum(dest, n - 1), axis=0)
    gathered = jnp.where(ok[:, None], gathered, 0)
    return jnp.zeros((T, y_flat.shape[1]), dtype).at[st].add(
        gathered * sw[:, None].astype(dtype))


def _expert_ffn(xbuf, wg, wu, wd, use_hints: bool = False):
    """(E, C, d) through per-expert SwiGLU.  ``use_hints`` applies the
    GSPMD logical-axis hints (dense path only — the shard_map paths place
    everything explicitly)."""
    g = jnp.einsum("ecd,edf->ecf", xbuf, wg)
    u = jnp.einsum("ecd,edf->ecf", xbuf, wu)
    h = jax.nn.silu(g) * u
    if use_hints:
        h = hint(h, "experts_act", None, "ff")
    return jnp.einsum("ecf,efd->ecd", h, wd)


def moe_ffn_dense(cfg: ArchConfig, lp, x) -> Tuple[jax.Array, jax.Array]:
    """Baseline GSPMD path: global dispatch, sharding via hints.

    The data-dependent scatter defeats GSPMD's sharding of the (T, d)
    token buffer — the compiler replicates/gathers it across the mesh.
    This is the paper-faithful 'let the runtime place it' baseline the
    §Perf hillclimb measures against.
    """
    b, s, d = x.shape
    E = cfg.num_experts
    T = b * s
    C = _capacity(cfg, T)
    xf = x.reshape(T, d)
    top_p, top_e, aux = _route(cfg, xf, lp["router"])
    xbuf, meta = _dispatch(xf, top_e, top_p, E, C)
    xbuf = hint(xbuf, "experts_act", None, None)
    y = _expert_ffn(xbuf, lp["we_gate"], lp["we_up"], lp["we_down"],
                    use_hints=True)
    out = _combine(y.reshape(E * C, d), meta, T, x.dtype)
    return hint(out.reshape(b, s, d), "batch", "seq", "embed"), aux


def _mesh_info():
    from repro.sharding_hints import active_mesh
    mesh = active_mesh()
    if mesh is None:
        return None
    names = mesh.axis_names
    batch_axes = tuple(a for a in ("pod", "data") if a in names)
    model_axis = "model" if "model" in names else None
    return mesh, names, batch_axes, model_axis


def moe_ffn_a2a(cfg: ArchConfig, lp, x) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel shard_map path (beyond-paper §Perf optimization).

    Tokens are dispatched LOCALLY per device shard (sort-based, same math
    as the dense path), then an explicit all-to-all along the ``model``
    axis moves each expert's slots to its owner; a reverse all-to-all
    brings results home.  Collective volume drops from 'replicate the
    global token buffer' to the intrinsic k*T*d dispatch bytes.

    Requires E %% model_axis == 0 (e.g. qwen3-moe: 128 %% 16).
    """
    from jax.sharding import PartitionSpec as P
    info = _mesh_info()
    if info is None:
        return moe_ffn_dense(cfg, lp, x)
    mesh, names, batch_axes, maxis = info
    b, s, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    m = mesh.shape[maxis]
    assert E % m == 0, (E, m)
    e_loc = E // m
    # shard seq over model when it divides; decode (s==1) keeps seq local
    seq_axis = maxis if s % m == 0 and s > 1 else None
    db = 1
    for a in batch_axes:
        db *= mesh.shape[a]

    xspec = P(batch_axes, seq_axis, None)
    rspec = P("data" if "data" in names else None, None)     # (d, E) fsdp
    wspec = P(maxis, "data" if "data" in names else None, None)

    def body(xl, router, wg, wu, wd):
        bl, sl, _ = xl.shape
        T_loc = bl * sl
        xf = xl.reshape(T_loc, d)
        router_f = lax.all_gather(router, "data", axis=0, tiled=True) \
            if "data" in names else router
        wg = lax.all_gather(wg, "data", axis=1, tiled=True) \
            if "data" in names else wg
        wu = lax.all_gather(wu, "data", axis=1, tiled=True) \
            if "data" in names else wu
        wd = lax.all_gather(wd, "data", axis=2, tiled=True) \
            if "data" in names else wd
        top_p, top_e, aux = _route(cfg, xf, router_f)
        C = _capacity(cfg, T_loc)
        xbuf, meta = _dispatch(xf, top_e, top_p, E, C)       # (E, C, d)
        # ship slots to expert owners along the model axis
        send = xbuf.reshape(m, e_loc, C, d)
        recv = lax.all_to_all(send, maxis, split_axis=0, concat_axis=0,
                              tiled=False)
        # recv: (m_peers, e_loc, C, d) -> (e_loc, m*C, d)
        xe = recv.transpose(1, 0, 2, 3).reshape(e_loc, m * C, d)
        y = _expert_ffn(xe, wg, wu, wd)                      # (e_loc, mC, d)
        back = y.reshape(e_loc, m, C, d).transpose(1, 0, 2, 3)
        got = lax.all_to_all(back, maxis, split_axis=0, concat_axis=0,
                             tiled=False)                    # (m, e_loc, C, d)
        y_home = got.reshape(E * C, d)
        out = _combine(y_home, meta, T_loc, x.dtype)
        axes_for_mean = tuple(a for a in (*batch_axes, seq_axis) if a)
        aux = lax.pmean(aux, axes_for_mean) if axes_for_mean else aux
        aux = lax.pmean(aux, maxis) if seq_axis is None else aux
        return out.reshape(bl, sl, d), aux

    out, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(xspec, rspec, wspec, wspec,
                  P(maxis, None, "data" if "data" in names else None)),
        out_specs=(xspec, P()),
        check_vma=False,
    )(x, lp["router"], lp["we_gate"], lp["we_up"], lp["we_down"])
    return out, aux


def moe_ffn_local(cfg: ArchConfig, lp, x) -> Tuple[jax.Array, jax.Array]:
    """Replicated-experts shard_map path for banks that do not divide the
    model axis (granite: 40 experts on 16).  Tokens shard over every mesh
    axis; each device runs ALL experts on its own tokens — zero dispatch
    collectives, expert weights replicated on the model axis (small-expert
    regime: granite d_ff=512 -> 126 MB/layer)."""
    from jax.sharding import PartitionSpec as P
    info = _mesh_info()
    if info is None:
        return moe_ffn_dense(cfg, lp, x)
    mesh, names, batch_axes, maxis = info
    b, s, d = x.shape
    E = cfg.num_experts
    msize = mesh.shape[maxis] if maxis else 1
    seq_axis = maxis if maxis and s % msize == 0 and s > 1 else None

    xspec = P(batch_axes, seq_axis, None)
    dshard = "data" if "data" in names else None

    def body(xl, router, wg, wu, wd):
        bl, sl, _ = xl.shape
        T_loc = bl * sl
        xf = xl.reshape(T_loc, d)
        if dshard:
            router = lax.all_gather(router, "data", axis=0, tiled=True)
            wg = lax.all_gather(wg, "data", axis=1, tiled=True)
            wu = lax.all_gather(wu, "data", axis=1, tiled=True)
            wd = lax.all_gather(wd, "data", axis=2, tiled=True)
        top_p, top_e, aux = _route(cfg, xf, router)
        C = _capacity(cfg, T_loc)
        xbuf, meta = _dispatch(xf, top_e, top_p, E, C)
        y = _expert_ffn(xbuf, wg, wu, wd)
        out = _combine(y.reshape(E * C, d), meta, T_loc, x.dtype)
        axes_for_mean = tuple(a for a in (*batch_axes, seq_axis) if a)
        aux = lax.pmean(aux, axes_for_mean) if axes_for_mean else aux
        aux = lax.pmean(aux, maxis) if seq_axis is None and maxis else aux
        return out.reshape(bl, sl, d), aux

    out, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(xspec, P(dshard, None), P(None, dshard, None),
                  P(None, dshard, None), P(None, None, dshard)),
        out_specs=(xspec, P()),
        check_vma=False,
    )(x, lp["router"], lp["we_gate"], lp["we_up"], lp["we_down"])
    return out, aux


def moe_ffn(cfg: ArchConfig, lp, x) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar).

    Implementation selected by the active sharding rules (§Perf):
    'dense' (baseline GSPMD), 'a2a' (expert-parallel all-to-all), 'local'
    (replicated experts).
    """
    from repro.sharding_hints import get_rule
    impl = get_rule("moe_impl", "dense")
    if impl == "a2a":
        return moe_ffn_a2a(cfg, lp, x)
    if impl == "local":
        return moe_ffn_local(cfg, lp, x)
    return moe_ffn_dense(cfg, lp, x)


def _moe_block(cfg: ArchConfig, lp, x):
    xn = cm.rms_norm(x, lp["ln2"], cfg.norm_eps)
    return moe_ffn(cfg, lp, xn)


def forward(cfg: ArchConfig, params, tokens, *, window: int = 0,
            remat: bool = True):
    x = tfm._embed(cfg, params, tokens)

    def layer(carry, lp):
        x, aux = carry
        a, _ = tfm.attn(cfg, lp, x, window=window)
        x = x + a
        m, aux_l = _moe_block(cfg, lp, x)
        return (x + m, aux + aux_l), None

    body = jax.checkpoint(layer) if remat else layer
    (x, aux), _ = lax.scan(body, (x, jnp.float32(0.0)), params["layers"])
    return tfm._logits(cfg, params, x), aux


def loss_fn(cfg: ArchConfig, params, batch, *, window: int = 0):
    logits, aux = forward(cfg, params, batch["tokens"], window=window)
    xent = cm.softmax_xent(logits[:, :-1], batch["labels"][:, 1:])
    loss = xent + cfg.router_aux_coef * aux / cfg.num_layers
    return loss, {"loss": loss, "xent": xent, "aux": aux}


init_cache = tfm.init_cache
cache_spec = tfm.cache_spec
cache_to_kv_dtype = tfm.cache_to_kv_dtype
cache_splice_paged = tfm.cache_splice_paged
paged_info = tfm.paged_info


def decode_step(cfg: ArchConfig, params, token, cache, pos, *,
                window: int = 0):
    # xs/ys cache streaming, bksd layout (see transformer.decode_step)
    x = tfm._embed(cfg, params, token)

    def layer(x, scanned):
        lp, ck, cv = scanned
        a, ck, cv = tfm.attn_decode(cfg, lp, x, ck, cv, pos, window=window)
        x = x + a
        m, _ = _moe_block(cfg, lp, x)
        return x + m, (ck, cv)

    x, (ck, cv) = lax.scan(layer, x, (params["layers"], cache["k"],
                                      cache["v"]))
    return tfm._logits(cfg, params, x), {"k": ck, "v": cv}


def decode_step_batch(cfg: ArchConfig, params, tokens, cache, pos, *,
                      window: int = 0, attn_backend=None):
    """Lane-major decode: tokens (B, 1); pos (B,) per-lane (see
    transformer.decode_step_batch).  The MoE block routes all B lane
    tokens through one dispatch instead of B single-token dispatches.
    An int8 cache (``k_scale`` leaf) takes the quantizing-write + q8
    attention path, same as the dense transformer; a paged cache
    (``page_table`` leaf) streams page pools through the scan."""
    x = tfm._embed(cfg, params, tokens)
    if "page_table" in cache:
        return _decode_step_batch_paged(cfg, params, x, cache, pos,
                                        window=window,
                                        attn_backend=attn_backend)
    quantized = "k_scale" in cache

    if quantized:
        def layer(x, scanned):
            lp, ck, cv, cks, cvs = scanned
            a, ck, cv, cks, cvs = tfm.attn_decode_batch(
                cfg, lp, x, ck, cv, pos, window=window,
                backend=attn_backend, cks=cks, cvs=cvs)
            x = x + a
            m, _ = _moe_block(cfg, lp, x)
            return x + m, (ck, cv, cks, cvs)

        x, (ck, cv, cks, cvs) = lax.scan(
            layer, x, (params["layers"], cache["k"], cache["v"],
                       cache["k_scale"], cache["v_scale"]))
        return tfm._logits(cfg, params, x), {"k": ck, "v": cv,
                                             "k_scale": cks,
                                             "v_scale": cvs}

    def layer(x, scanned):
        lp, ck, cv = scanned
        a, ck, cv = tfm.attn_decode_batch(cfg, lp, x, ck, cv, pos,
                                          window=window,
                                          backend=attn_backend)
        x = x + a
        m, _ = _moe_block(cfg, lp, x)
        return x + m, (ck, cv)

    x, (ck, cv) = lax.scan(layer, x, (params["layers"], cache["k"],
                                      cache["v"]))
    return tfm._logits(cfg, params, x), {"k": ck, "v": cv}


def _decode_step_batch_paged(cfg: ArchConfig, params, x, cache, pos, *,
                             window: int = 0, attn_backend=None):
    """Paged scan bodies (see transformer._decode_step_batch_paged) with
    the MoE block in place of the dense MLP."""
    pt = cache["page_table"]
    quantized = "k_scale_pages" in cache

    if quantized:
        def layer(x, scanned):
            lp, ck, cv, cks, cvs = scanned
            a, ck, cv, cks, cvs = tfm.attn_decode_batch(
                cfg, lp, x, ck, cv, pos, window=window,
                backend=attn_backend, cks=cks, cvs=cvs, page_table=pt)
            x = x + a
            m, _ = _moe_block(cfg, lp, x)
            return x + m, (ck, cv, cks, cvs)

        x, (ck, cv, cks, cvs) = lax.scan(
            layer, x, (params["layers"], cache["k_pages"],
                       cache["v_pages"], cache["k_scale_pages"],
                       cache["v_scale_pages"]))
        return tfm._logits(cfg, params, x), {
            "k_pages": ck, "v_pages": cv, "k_scale_pages": cks,
            "v_scale_pages": cvs, "page_table": pt}

    def layer(x, scanned):
        lp, ck, cv = scanned
        a, ck, cv = tfm.attn_decode_batch(cfg, lp, x, ck, cv, pos,
                                          window=window,
                                          backend=attn_backend,
                                          page_table=pt)
        x = x + a
        m, _ = _moe_block(cfg, lp, x)
        return x + m, (ck, cv)

    x, (ck, cv) = lax.scan(layer, x, (params["layers"], cache["k_pages"],
                                      cache["v_pages"]))
    return tfm._logits(cfg, params, x), {"k_pages": ck, "v_pages": cv,
                                         "page_table": pt}


def prefill(cfg: ArchConfig, params, tokens, cache_len: int, *,
            window: int = 0, cache_dtype=jnp.bfloat16):
    b, s = tokens.shape
    x = tfm._embed(cfg, params, tokens)

    def layer(x, lp):
        a, (kk, vv) = tfm.attn(cfg, lp, x, window=window)
        x = x + a
        m, _ = _moe_block(cfg, lp, x)
        return x + m, (kk.astype(cache_dtype), vv.astype(cache_dtype))

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
        ck = jnp.roll(ck, s % cache_len, axis=3)
        cv = jnp.roll(cv, s % cache_len, axis=3)
    return tfm._logits(cfg, params, x), {"k": ck, "v": cv}
