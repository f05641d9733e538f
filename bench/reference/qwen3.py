"""Plain reference of a Qwen3 dense decoder, and the weights both it and
the program under test are given.

Follows the published Qwen3 description (hf:Qwen/Qwen3-8B, modeling_qwen3):
pre-norm RMSNorm blocks, grouped-query attention with a per-head RMSNorm
on queries and keys before rotary embeddings (rotate-half, ``rope_theta``),
causal softmax attention scaled by 1/sqrt(head_dim), SwiGLU MLP, final
RMSNorm and an output head (the embedding, transposed, when tied).

It is written in float32 with every product at ``highest`` precision, one
layer at a time so that it fits beside the weights, and imports nothing of
the program.  The only thing it shares with the program is the weights,
which this module makes from the seed.  They are laid out as the program
takes them: layer tensors stacked along a leading layer axis, and each
RMSNorm gain stored as its offset from 1 (gain = 1 + stored value).

``control=True`` computes the same model with every linear layer and the
head in float8 (e4m3: activations scaled per token, weights per output
channel, products accumulated in float32), the step below the bfloat16
the configurations state.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
BUCKET = 512            # sequences are right-padded to a multiple of this
HEAD_ROWS = 512         # output-head rows computed per call
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def _shapes(m: dict) -> dict:
    d, hd, f, v = (m["hidden_size"], m["head_dim"], m["intermediate_size"],
                   m["vocab_size"])
    q, kv, L = (m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd,
                m["num_hidden_layers"])
    layers = {"ln1": (L, d), "wq": (L, d, q), "wk": (L, d, kv),
              "wv": (L, d, kv), "wo": (L, q, d), "q_norm": (L, hd),
              "k_norm": (L, hd), "ln2": (L, d), "w_gate": (L, d, f),
              "w_up": (L, d, f), "w_down": (L, f, d)}
    top = {"embed": (v, d), "final_ln": (d,)}
    if not m["tie_word_embeddings"]:
        top["unembed"] = (d, v)
    return {**top, "layers": layers}


def _std(name: str, shape) -> float:
    if name == "embed":
        return 0.02                      # initializer_range of the config
    if name in ("ln1", "ln2", "final_ln", "q_norm", "k_norm"):
        return 0.1                       # gains 1 +- 0.1
    return 1.0 / math.sqrt(shape[-2])    # fan-in of the product


def seed_key(seed: int):
    """A PRNG key for any whole-number seed, including ones wider than
    32 bits."""
    words = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def make_params(m: dict, seed: int, dtype=jnp.bfloat16):
    """Random weights from ``seed``, made on the device in one jitted call
    in the type they are served in."""
    shapes = _shapes(m)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))

    @jax.jit
    def build(key):
        leaves = []
        for i, (path, shape) in enumerate(flat):
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            leaves.append((x * _std(path[-1].key, shape)).astype(dtype))
        return jax.tree_util.tree_unflatten(tree, leaves)

    return build(seed_key(seed))


# -- forward ------------------------------------------------------------------


def _rms(x, gain, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + gain.astype(jnp.float32))


def _fp8(x, axis):
    """Round ``x`` to float8 with one scale per slice along ``axis``."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-12) / FP8_MAX
    return (x / s).astype(FP8).astype(jnp.float32) * s


def _linear(x, w, control):
    w = w.astype(jnp.float32)
    if control:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rope(x, theta):
    s, _, hd = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("cfg", "control"))
def _layer(x, lp, *, cfg, control):
    heads, kvh, hd, theta, eps = cfg
    s = x.shape[0]
    h = _rms(x, lp["ln1"], eps)
    q = _linear(h, lp["wq"], control).reshape(s, heads, hd)
    k = _linear(h, lp["wk"], control).reshape(s, kvh, hd)
    v = _linear(h, lp["wv"], control).reshape(s, kvh, hd)
    q = _rope(_rms(q, lp["q_norm"], eps), theta)
    k = _rope(_rms(k, lp["k_norm"], eps), theta)
    k = jnp.repeat(k, heads // kvh, axis=1)
    v = jnp.repeat(v, heads // kvh, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / math.sqrt(hd)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(s, heads * hd)
    x = x + _linear(o, lp["wo"], control)
    h = _rms(x, lp["ln2"], eps)
    g = jax.nn.silu(_linear(h, lp["w_gate"], control))
    return x + _linear(g * _linear(h, lp["w_up"], control), lp["w_down"],
                       control)


@jax.jit
def _embed(emb, tokens):
    return emb[tokens].astype(jnp.float32)


@partial(jax.jit, static_argnames=("eps",))
def _head(h, gain, w, served, *, eps):
    """For a block of positions: the reference's best logit and its logit
    of the served token."""
    logits = _linear(_rms(h, gain, eps), w, False)
    at = jnp.take_along_axis(logits, served[:, None], axis=1)[:, 0]
    return logits.max(axis=-1), at


@partial(jax.jit, static_argnames=("eps",))
def _control_head(h, hc, gain, w, *, eps):
    """The reference's logit of the token that the control, from its own
    hidden states ``hc``, puts first."""
    pick = _linear(_rms(hc, gain, eps), w, True).argmax(axis=-1)
    logits = _linear(_rms(h, gain, eps), w, False)
    return jnp.take_along_axis(logits, pick[:, None], axis=1)[:, 0]


def _hidden(m, params, tokens, control):
    cfg = (m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"],
           float(m["rope_theta"]), float(m["rms_norm_eps"]))
    x = _embed(params["embed"], tokens)
    for i in range(m["num_hidden_layers"]):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        x = _layer(x, lp, cfg=cfg, control=control)
    return x


def served_gaps(m: dict, params, prompt, served, control: bool = False):
    """Run the reference over ``prompt`` followed by the ``served``
    tokens and return, for each served token, how far its logit lies
    below the reference's best at that position.  With ``control`` also
    return the same gap for the token that the float8 control puts first
    at each position (the control reads the same prompt and tokens)."""
    seq = list(prompt) + list(served[:-1])
    n, start = len(served), len(prompt) - 1
    pad = -(-len(seq) // BUCKET) * BUCKET
    tokens = jnp.asarray(seq + [0] * (pad - len(seq)), jnp.int32)
    w = params["embed"].T if m["tie_word_embeddings"] else params["unembed"]
    gain, eps = params["final_ln"], float(m["rms_norm_eps"])
    target = np.zeros(pad, np.int32)
    target[start:start + n] = served
    h = _hidden(m, params, tokens, False)
    hc = _hidden(m, params, tokens, True) if control else None
    gaps, ctl_gaps = [], []
    for r0 in range(start // HEAD_ROWS * HEAD_ROWS, start + n, HEAD_ROWS):
        rows = slice(r0, r0 + HEAD_ROWS)
        lo, hi = max(start - r0, 0), min(start + n - r0, HEAD_ROWS)
        best, at = (np.asarray(a) for a in _head(
            h[rows], gain, w, jnp.asarray(target[rows]), eps=eps))
        gaps.append(best[lo:hi] - at[lo:hi])
        if control:
            at_pick = np.asarray(_control_head(h[rows], hc[rows], gain, w,
                                               eps=eps))
            ctl_gaps.append(best[lo:hi] - at_pick[lo:hi])
    out = {"served": np.concatenate(gaps)}
    if control:
        out["control"] = np.concatenate(ctl_gaps)
    return out
