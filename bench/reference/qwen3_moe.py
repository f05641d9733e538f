"""Plain reference of a Qwen3 mixture-of-experts decoder at one chip's
share of its experts, and the weights both it and the program under test
are given.

Follows the published description (hf:Qwen/Qwen3-235B-A22B,
modeling_qwen3_moe): pre-norm RMSNorm blocks; grouped-query attention
with a per-head RMSNorm on queries and keys before rotary embeddings, as
in the dense Qwen3 (``reference/qwen3.py``, whose RMSNorm, rotary
embedding, linear layers, embedding and output head this module uses);
and in place of the dense MLP a sparse block: router logits over the
whole bank (``published.num_experts``), softmax in float32, the top
``num_experts_per_tok``, their weights renormalised over those chosen
(``norm_topk_prob``), and the weighted sum of the chosen experts' SwiGLU
outputs.  Of those experts only the ones this chip holds
(``deployment.held_experts``) are computed and summed: what the others
would add is left out, as the program leaves it out.  Routing is decided
on the reference's own float32 hidden states.

Written in float32 with every product at ``highest`` precision, one layer
at a time, each held expert over every position (the router weight is 0
where a position did not choose it); it imports nothing of the program.
``control=True`` computes every linear layer, the router and the experts
included, in float8 (e4m3) as ``reference/qwen3.py`` does.
"""
from __future__ import annotations

import importlib.util
import math
import pathlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _dense():
    path = pathlib.Path(__file__).with_name("qwen3.py")
    spec = importlib.util.spec_from_file_location(
        "bench_reference_qwen3_of_moe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


q3 = _dense()
HI = q3.HI


def _shapes(m: dict) -> dict:
    d, hd, f, v = (m["hidden_size"], m["head_dim"],
                   m["moe_intermediate_size"], m["vocab_size"])
    q, kv, L = (m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd,
                m["num_hidden_layers"])
    e_held, bank = m["num_experts"], m["published"]["num_experts"]
    layers = {"ln1": (L, d), "wq": (L, d, q), "wk": (L, d, kv),
              "wv": (L, d, kv), "wo": (L, q, d), "q_norm": (L, hd),
              "k_norm": (L, hd), "ln2": (L, d), "router": (L, d, bank),
              "we_gate": (L, e_held, d, f), "we_up": (L, e_held, d, f),
              "we_down": (L, e_held, f, d)}
    top = {"embed": (v, d), "final_ln": (d,)}
    if not m["tie_word_embeddings"]:
        top["unembed"] = (d, v)
    return {**top, "layers": layers}


def make_params(m: dict, seed: int, dtype=None):
    """Random weights from ``seed``, made on the device in one jitted call
    in the type they are served in (``torch_dtype`` unless ``dtype`` is
    given), laid out as the program takes them: layer tensors stacked on
    a leading layer axis, the held experts on the next, the router over
    the whole bank."""
    dtype = jnp.dtype(dtype or m["torch_dtype"])
    flat, tree = jax.tree_util.tree_flatten_with_path(
        _shapes(m), is_leaf=lambda x: isinstance(x, tuple))

    @jax.jit
    def build(key):
        leaves = []
        for i, (path, shape) in enumerate(flat):
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            leaves.append((x * q3._std(path[-1].key, shape)).astype(dtype))
        return jax.tree_util.tree_unflatten(tree, leaves)

    return build(q3.seed_key(seed))


# -- forward ------------------------------------------------------------------


def _attention(x, lp, cfg, control):
    heads, kvh, hd, theta, eps = cfg
    s = x.shape[0]
    h = q3._rms(x, lp["ln1"], eps)
    q = q3._linear(h, lp["wq"], control).reshape(s, heads, hd)
    k = q3._linear(h, lp["wk"], control).reshape(s, kvh, hd)
    v = q3._linear(h, lp["wv"], control).reshape(s, kvh, hd)
    q = q3._rope(q3._rms(q, lp["q_norm"], eps), theta)
    k = q3._rope(q3._rms(k, lp["k_norm"], eps), theta)
    k = jnp.repeat(k, heads // kvh, axis=1)
    v = jnp.repeat(v, heads // kvh, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / math.sqrt(hd)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(s, heads * hd)
    return q3._linear(o, lp["wo"], control)


def _route(h, router, k, control):
    """Top-``k`` experts of each position and their renormalised
    weights."""
    probs = jax.nn.softmax(q3._linear(h, router, control), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)
    return top_p / top_p.sum(-1, keepdims=True), top_e


@partial(jax.jit, static_argnames=("cfg", "moe", "control"))
def _layer(x, lp, *, cfg, moe, control):
    k, first = moe
    x = x + _attention(x, lp, cfg, control)
    h = q3._rms(x, lp["ln2"], cfg[-1])
    top_p, top_e = _route(h, lp["router"], k, control)
    out = jnp.zeros_like(x)
    for e in range(lp["we_gate"].shape[0]):
        w = jnp.where(top_e == first + e, top_p, 0.0).sum(-1)
        g = jax.nn.silu(q3._linear(h, lp["we_gate"][e], control))
        y = q3._linear(g * q3._linear(h, lp["we_up"][e], control),
                       lp["we_down"][e], control)
        out = out + w[:, None] * y
    return x + out, top_e


def _hidden(m, params, tokens, control, routes=None):
    cfg = (m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"],
           float(m["rope_theta"]), float(m["rms_norm_eps"]))
    moe = (m["num_experts_per_tok"], m["deployment"]["held_experts"][0])
    x = q3._embed(params["embed"], tokens)
    for i in range(m["num_hidden_layers"]):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        x, top_e = _layer(x, lp, cfg=cfg, moe=moe, control=control)
        if routes is not None:
            routes.append(top_e)
    return x


def routes(m: dict, params, tokens):
    """Each layer's top-k expert ids of every position, (L, S, k), as the
    reference routes ``tokens``."""
    got = []
    _hidden(m, params, jnp.asarray(tokens, jnp.int32), False, got)
    return np.stack([np.asarray(t) for t in got])


def logits(m: dict, params, tokens):
    """Float32 logits of every position of ``tokens``, (S, vocab)."""
    h = _hidden(m, params, jnp.asarray(tokens, jnp.int32), False)
    w = params["embed"].T if m["tie_word_embeddings"] else params["unembed"]
    return np.asarray(q3._linear(q3._rms(h, params["final_ln"],
                                         float(m["rms_norm_eps"])),
                                 w, False))


def served_gaps(m: dict, params, prompt, served, control: bool = False):
    """As ``reference/qwen3.served_gaps``: for each served token, how far
    its logit lies below the reference's best at its position, and with
    ``control`` the same for the token the float8 control puts first."""
    seq = list(prompt) + list(served[:-1])
    n, start = len(served), len(prompt) - 1
    pad = -(-len(seq) // q3.BUCKET) * q3.BUCKET
    tokens = jnp.asarray(seq + [0] * (pad - len(seq)), jnp.int32)
    w = params["embed"].T if m["tie_word_embeddings"] else params["unembed"]
    gain, eps = params["final_ln"], float(m["rms_norm_eps"])
    target = np.zeros(pad, np.int32)
    target[start:start + n] = served
    h = _hidden(m, params, tokens, False)
    hc = _hidden(m, params, tokens, True) if control else None
    gaps, ctl_gaps = [], []
    rows_of = q3.HEAD_ROWS
    for r0 in range(start // rows_of * rows_of, start + n, rows_of):
        rows = slice(r0, r0 + rows_of)
        lo, hi = max(start - r0, 0), min(start + n - r0, rows_of)
        best, at = (np.asarray(a) for a in q3._head(
            h[rows], gain, w, jnp.asarray(target[rows]), eps=eps))
        gaps.append(best[lo:hi] - at[lo:hi])
        if control:
            at_pick = np.asarray(q3._control_head(h[rows], hc[rows], gain,
                                                  w, eps=eps))
            ctl_gaps.append(best[lo:hi] - at_pick[lo:hi])
    out = {"served": np.concatenate(gaps)}
    if control:
        out["control"] = np.concatenate(ctl_gaps)
    return out
