"""Qwen3 dense decoders (``model_type`` ``"qwen3"``): how a published
``config.json`` maps onto the program's ``ArchConfig``, and the
operations and bytes of the served work, from shapes alone.

A configuration file is checked key by key against this module: a key
that it neither maps nor knows to be inert, or a fixed key set to another
value (a bias, another activation, a sliding window, rope scaling), is an
error, so that no configuration of another shape runs silently as a
Qwen3.  Another family (a mixture of experts, a recurrent model) gets a
module of its own, ``families/<model_type>.py``, with the same functions.

The counts are what the algorithm needs, not what the program happens to
execute: a prefill needs the output head for its last position only, and
causal attention needs the lower triangle of the score matrix.
"""
from __future__ import annotations

# sizes the mapping reads
SIZES = ("hidden_size", "intermediate_size", "num_hidden_layers",
         "num_attention_heads", "num_key_value_heads", "head_dim",
         "vocab_size", "tie_word_embeddings", "rope_theta", "rms_norm_eps",
         "torch_dtype")
# keys this module describes only at these values
FIXED = {"model_type": "qwen3", "architectures": ["Qwen3ForCausalLM"],
         "hidden_act": "silu", "attention_bias": False,
         "attention_dropout": 0.0, "rope_scaling": None,
         "sliding_window": None, "use_sliding_window": False}
# keys with no bearing on the served computation: token ids, the
# initialiser of training, limits the cells stay within, a cache switch
INERT = ("bos_token_id", "eos_token_id", "initializer_range",
         "max_position_embeddings", "max_window_layers", "use_cache")


def validate(m: dict, harness_keys) -> None:
    """Raises ``ValueError`` for a key of ``m`` that is neither the
    harness's, mapped, fixed nor inert, and for a fixed key at another
    value."""
    known = set(harness_keys) | set(SIZES) | set(FIXED) | set(INERT)
    unknown = sorted(set(m) - known)
    if unknown:
        raise ValueError(f"keys not described by the qwen3 family: {unknown}")
    missing = sorted(set(SIZES) - set(m))
    if missing:
        raise ValueError(f"qwen3 configuration lacks {missing}")
    wrong = {k: m[k] for k, v in FIXED.items() if k in m and m[k] != v}
    if wrong:
        raise ValueError(f"qwen3 family describes only {FIXED}; got {wrong}")


def arch_config(m: dict):
    """The program's ``ArchConfig``: a dense decoder with per-head RMSNorm
    on queries and keys (Qwen3's architecture, which ``config.json`` does
    not spell out), every size from the file."""
    from repro.configs.base import ArchConfig
    return ArchConfig(
        name=m["model_type"], family="dense",
        num_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        tie_embeddings=m["tie_word_embeddings"], qk_norm=True,
        rope_theta=float(m["rope_theta"]), norm_eps=float(m["rms_norm_eps"]),
        dtype=m["torch_dtype"])


def _dims(m: dict):
    d, hd = m["hidden_size"], m["head_dim"]
    return (d, hd, m["num_attention_heads"] * hd,
            m["num_key_value_heads"] * hd, m["intermediate_size"])


def layer_params(m: dict) -> int:
    """Parameters of one decoder layer (projections and norms)."""
    d, hd, q, kv, f = _dims(m)
    return d * q + 2 * d * kv + q * d + 3 * d * f + 2 * d + 2 * hd


def model_params(m: dict) -> int:
    """Parameters of the whole model as configured."""
    emb = m["vocab_size"] * m["hidden_size"]
    head = 0 if m["tie_word_embeddings"] else emb
    return (m["num_hidden_layers"] * layer_params(m) + emb + head
            + m["hidden_size"])


def _matmul_flops_per_token(m: dict) -> int:
    d, _, q, kv, f = _dims(m)
    return 2 * m["num_hidden_layers"] * (d * q + 2 * d * kv + q * d + 3 * d * f)


def _head_flops(m: dict) -> int:
    return 2 * m["hidden_size"] * m["vocab_size"]


def attn_flops(m: dict, n_keys: int) -> int:
    """Score and value products of one query against ``n_keys`` keys,
    over every layer."""
    return 4 * m["num_hidden_layers"] * m["num_attention_heads"] \
        * m["head_dim"] * n_keys


def prefill_flops(m: dict, plen: int) -> int:
    """One prompt of ``plen`` tokens: every projection for every token,
    causal attention (token ``i`` sees ``i + 1`` keys), and the output head
    for the last position, which yields the first token."""
    causal_keys = plen * (plen + 1) // 2
    return (plen * _matmul_flops_per_token(m) + attn_flops(m, 1) * causal_keys
            + _head_flops(m))


def decode_flops(m: dict, valid_lens) -> int:
    """One batched decode step: each lane's token through every
    projection and the head, attending to its ``valid`` cached tokens."""
    per_tok = _matmul_flops_per_token(m) + _head_flops(m)
    return sum(per_tok + attn_flops(m, v) for v in valid_lens)


def kv_bytes_per_token(m: dict, kv_itemsize: int) -> int:
    """Bytes of K and V that one cached token holds, over every layer."""
    return 2 * m["num_hidden_layers"] * m["num_key_value_heads"] \
        * m["head_dim"] * kv_itemsize


def decode_attn_cost(m: dict, valid_lens, kv_itemsize: int):
    """(bytes, flops) that decode attention needs in one step: each lane
    reads the K/V of its valid tokens once, whatever reads them."""
    n = sum(valid_lens)
    return n * kv_bytes_per_token(m, kv_itemsize), attn_flops(m, n)
