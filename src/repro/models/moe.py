"""Mixture-of-Experts decoder (qwen3-moe, granite-moe families).

Token-choice top-k routing: softmax over the whole bank's router logits
in f32, the top k, their weights renormalised over the k chosen.

Serving (``prefill``, ``decode_step``, ``decode_step_batch``) is
dropless and holds a stated range of the bank (``cfg.held_experts``;
the whole bank when unset), as one chip of an expert-parallel
deployment would: every token routes over all ``num_experts``, the
(token, expert) pairs whose expert is held are sorted into per-expert
groups, and the grouped expert kernel (``kernels/grouped_ffn``) computes
exactly those rows.  A token's output is its held experts' weighted
SwiGLU outputs; what absent experts would add is left out.  Nothing
depends on a token's batch neighbours.

Training (``forward``/``loss_fn``) holds the whole bank and keeps
sort-based capacity dispatch: tokens are argsorted by expert id into an
(E, C, d) buffer, each expert runs a dense SwiGLU over its slice, and
tokens beyond capacity C are dropped (GShard/Switch semantics,
``capacity_factor`` sets the slack).

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


# device-side routing counters of the serving path, in the order of the
# ``stats`` vector its steps return: routed (token, held expert) rows
# computed, layer calls x held experts (summed), and the most rows one
# held expert took in one call (a maximum)
ROUTING_STATS = ("moe.rows_here", "moe.expert_calls", "moe.rows_max")
MAX_TILE = 256     # rows of one block of an expert's group, at most
EXPERTS = ("we_gate", "we_up", "we_down")


def param_template(cfg: ArchConfig):
    """The router spans the whole bank (``num_experts`` outputs); the
    expert weights only the experts held here."""
    L, d, f = cfg.num_layers, cfg.d_model, cfg.d_ff
    E = cfg.expert_range[1]
    t = {
        "embed": P((cfg.vocab_size, d), ("tp_vocab", "fsdp"), "embed"),
        "final_ln": P((d,), (None,), "zeros"),
        "layers": {
            **tfm._attn_template(cfg, L),
            "ln2": P((L, d), (None, None), "zeros"),
            "router": P((L, d, cfg.num_experts), (None, "fsdp", None)),
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


def _tile(tokens: int, dtype) -> int:
    """Rows of one block of an expert's group: a whole group of a batch
    of up to ``MAX_TILE`` tokens (an expert takes each token at most
    once), in whole sublane tiles."""
    from repro.kernels.grouped_ffn import sublane_rows
    sub = sublane_rows(dtype)
    return min(-(-tokens // sub) * sub, MAX_TILE)


def _grouped_ffn(x, wg, wu, wd, start, rows, layer, *, tile, chunks):
    """The grouped expert kernel on a TPU, its jnp oracle elsewhere."""
    if jax.default_backend() == "tpu":
        from repro.kernels import ops as kops
        return kops.grouped_expert_ffn(x, wg, wu, wd, start, rows, layer,
                                       tile=tile, chunks=chunks)
    from repro.kernels.ref import grouped_ffn_ref
    return grouped_ffn_ref(x, wg, wu, wd, start, rows, layer, tile=tile)


def moe_ffn_held(cfg: ArchConfig, lp, x, layer=None):
    """The serving path's expert layer, dropless: x (B, S, d) ->
    (the held experts' part of the output (B, S, d), routing counters
    (3,) int32 in ``ROUTING_STATS`` order).

    Routing is over the whole bank; the (token, choice) pairs whose
    expert is held are laid out by expert in groups of whole ``tile``-row
    blocks (one spare block last) and run through the grouped kernel,
    which computes each group's rows only.  Pairs of absent experts add
    nothing.  With ``layer``, ``lp``'s expert weights are the whole
    stack of layers, which the kernel reads in place at that index."""
    if layer is None:
        lp = {**lp, **{k: lp[k][None] for k in EXPERTS}}
        layer = 0
    b, s, d = x.shape
    T, k = b * s, cfg.experts_per_token
    first, n_held = cfg.expert_range
    tile = _tile(T, x.dtype)
    n_rows = ((T * min(k, n_held)) // tile + n_held + 1) * tile
    xf = x.reshape(T, d)
    with jax.named_scope("moe_route"):
        top_p, top_e, _ = _route(cfg, xf, lp["router"])
        local = top_e.reshape(-1) - first                     # (T*k,)
        held = (local >= 0) & (local < n_held)
        onehot = jax.nn.one_hot(jnp.where(held, local, -1), n_held,
                                dtype=jnp.int32)              # (T*k, E_h)
        rows = onehot.sum(0)
        rank = (jnp.cumsum(onehot, 0) * onehot).sum(-1) - 1
        blocks = -(-rows // tile)
        start = jnp.cumsum(blocks) - blocks
        dest = jnp.where(held, start[jnp.clip(local, 0, n_held - 1)] * tile
                         + rank, n_rows)
        # each row of the layout names its token (T: none, a zero row)
        src = jnp.full((n_rows,), T, jnp.int32).at[dest].set(
            jnp.arange(T * k, dtype=jnp.int32) // k, mode="drop")
        xbuf = jnp.concatenate([xf, jnp.zeros((1, d), x.dtype)])[src]
    with jax.named_scope("moe_experts"):
        y = _grouped_ffn(xbuf, lp["we_gate"], lp["we_up"], lp["we_down"],
                         start, rows, layer, tile=tile, chunks=-(-T // tile))
    with jax.named_scope("moe_route"):
        yk = jnp.take(y, jnp.minimum(dest, n_rows - 1), axis=0)
        w = jnp.where(held, top_p.reshape(-1), 0.0)[:, None]
        out = jnp.where(held[:, None], yk.astype(jnp.float32) * w, 0.0)
        out = out.reshape(T, k, d).sum(1).astype(x.dtype).reshape(b, s, d)
    stats = jnp.stack([rows.sum(), jnp.int32(n_held), rows.max()])
    return out, stats.astype(jnp.int32)


def moe_ffn(cfg: ArchConfig, lp, x) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar).

    Implementation selected by the active sharding rules (§Perf):
    'dense' (baseline GSPMD), 'a2a' (expert-parallel all-to-all), 'local'
    (replicated experts).
    """
    from repro.sharding_hints import get_rule
    if cfg.expert_range != (0, cfg.num_experts):
        raise ValueError("training dispatch holds the whole expert bank; "
                         f"got held_experts={cfg.held_experts}")
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


def _moe_serve(cfg: ArchConfig, lp, experts, x):
    """The expert block of layer ``lp["layer"]`` of a serving scan."""
    with jax.named_scope("mlp"):
        return moe_ffn_held(cfg, {**lp, **experts},
                            cm.rms_norm(x, lp["ln2"], cfg.norm_eps),
                            layer=lp["layer"])


def _scanned(params):
    """A serving scan's per-layer inputs, and the expert weights it leaves
    whole: the grouped kernel reads them from the stack, where a scanned
    slice would be copied every layer before the kernel could read it."""
    layers = params["layers"]
    rest = {k: v for k, v in layers.items() if k not in EXPERTS}
    rest["layer"] = jnp.arange(layers["ln2"].shape[0], dtype=jnp.int32)
    return rest, {k: layers[k] for k in EXPERTS}


def _fold_stats(stats):
    """Per-layer routing counters (L, 3) -> one step's (3,)."""
    return jnp.concatenate([stats[:, :2].sum(0), stats[:, 2:].max(0)])


init_cache = tfm.init_cache
cache_spec = tfm.cache_spec
cache_to_kv_dtype = tfm.cache_to_kv_dtype
cache_splice_paged = tfm.cache_splice_paged
paged_info = tfm.paged_info


def decode_step(cfg: ArchConfig, params, token, cache, pos, *,
                window: int = 0):
    # xs/ys cache streaming, bksd layout (see transformer.decode_step)
    x = tfm._embed(cfg, params, token)
    layers, experts = _scanned(params)

    def layer(x, scanned):
        lp, ck, cv = scanned
        a, ck, cv = tfm.attn_decode(cfg, lp, x, ck, cv, pos, window=window)
        x = x + a
        m, _ = _moe_serve(cfg, lp, experts, x)
        return x + m, (ck, cv)

    x, (ck, cv) = lax.scan(layer, x, (layers, cache["k"], cache["v"]))
    return tfm._logits(cfg, params, x), {"k": ck, "v": cv}


def decode_step_batch(cfg: ArchConfig, params, tokens, cache, pos, *,
                      window: int = 0, attn_backend=None,
                      with_stats: bool = False):
    """Lane-major decode: tokens (B, 1); pos (B,) per-lane (see
    transformer.decode_step_batch), the held experts' dropless layer in
    place of the dense MLP.  The cache's leaves pick the attention path:
    ring or paged (``page_table``), float or int8 (scale leaves).  Ring
    buffers stream through the layer scan as xs/ys; page pools are
    carried whole and addressed by layer index
    (``transformer.decode_layers_paged``), so the scheduler's step,
    which donates its state, updates them in place and no caller may
    keep a leaf of a state it has passed to a step.  With
    ``with_stats`` also returns the step's routing counters
    (``ROUTING_STATS``)."""
    with jax.named_scope("embed"):
        x = tfm._embed(cfg, params, tokens)
    layers, experts = _scanned(params)

    def ffn(lp, x):
        m, stats = _moe_serve(cfg, lp, experts, x)
        return x + m, stats

    if "page_table" in cache:
        x, cache, stats = tfm.decode_layers_paged(
            cfg, layers, x, cache, pos, ffn, window=window,
            attn_backend=attn_backend)
    else:
        names = tuple(n for n in ("k", "v", "k_scale", "v_scale")
                      if n in cache)

        def layer(x, scanned):
            lp, ck, cv, *scales = scanned
            kw = dict(cks=scales[0], cvs=scales[1]) if scales else {}
            a, *kv = tfm.attn_decode_batch(cfg, lp, x, ck, cv, pos,
                                           window=window,
                                           backend=attn_backend, **kw)
            x, stats = ffn(lp, x + a)
            return x, (tuple(kv), stats)

        x, (kv, stats) = lax.scan(layer, x,
                                  (layers, *(cache[n] for n in names)))
        cache = {**cache, **dict(zip(names, kv))}
    with jax.named_scope("head"):
        logits = tfm._logits(cfg, params, x)
    if with_stats:
        return logits, cache, _fold_stats(stats)
    return logits, cache


def prefill(cfg: ArchConfig, params, tokens, cache_len: int, *,
            window: int = 0, cache_dtype=jnp.bfloat16,
            with_stats: bool = False):
    b, s = tokens.shape
    x = tfm._embed(cfg, params, tokens)
    layers, experts = _scanned(params)

    def layer(x, lp):
        a, (kk, vv) = tfm.attn(cfg, lp, x, window=window)
        x = x + a
        m, stats = _moe_serve(cfg, lp, experts, x)
        return x + m, (kk.astype(cache_dtype), vv.astype(cache_dtype), stats)

    x, (ks, vs, stats) = lax.scan(layer, x, layers)
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
    out = (tfm._logits(cfg, params, x), {"k": ck, "v": cv})
    return (*out, _fold_stats(stats)) if with_stats else out
