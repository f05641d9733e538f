"""Qwen3 mixture-of-experts decoders (``model_type`` ``"qwen3_moe"``): how
a published ``config.json`` at one chip's share of an expert-parallel
deployment maps onto the program's ``ArchConfig``, and the operations and
bytes of the served work, from shapes alone.

The file's ``num_experts`` is the number of experts this chip holds,
``published.num_experts`` the router's width (the whole bank), and
``deployment.held_experts`` the held range as ``[first id, count]``.
Every layer is sparse (``mlp_only_layers`` empty, ``decoder_sparse_step``
1), so ``intermediate_size``, the width of a dense layer, is inert.

As in ``families/qwen3.py``, a key that this module neither maps nor
knows to be inert, or a fixed key at another value, is an error.  The
counts are what the algorithm needs: a token's experts are its top
``num_experts_per_tok`` of the whole bank, so a chip holding ``E_held``
of ``E`` experts computes ``num_experts_per_tok * E_held / E`` expert
rows a token on average, whatever implements them.  Attention counts
are those of the dense family.
"""
from __future__ import annotations

import importlib.util
import pathlib

SIZES = ("hidden_size", "moe_intermediate_size", "num_hidden_layers",
         "num_attention_heads", "num_key_value_heads", "head_dim",
         "vocab_size", "num_experts", "num_experts_per_tok",
         "tie_word_embeddings", "rope_theta", "rms_norm_eps",
         "torch_dtype")
FIXED = {"model_type": "qwen3_moe",
         "architectures": ["Qwen3MoeForCausalLM"],
         "hidden_act": "silu", "attention_bias": False,
         "attention_dropout": 0.0, "rope_scaling": None,
         "sliding_window": None, "use_sliding_window": False,
         "norm_topk_prob": True, "mlp_only_layers": [],
         "decoder_sparse_step": 1}
# token ids, training-only settings (initialiser, the router's balance
# loss and its output), limits the cells stay within, a cache switch,
# and the width of dense layers that this model has none of
INERT = ("bos_token_id", "eos_token_id", "initializer_range",
         "max_position_embeddings", "max_window_layers", "use_cache",
         "router_aux_loss_coef", "output_router_logits",
         "intermediate_size")


def _dense():
    path = pathlib.Path(__file__).with_name("qwen3.py")
    spec = importlib.util.spec_from_file_location("bench_family_qwen3_of_moe",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_q3 = _dense()
# attention and the KV cache are the dense family's
attn_flops = _q3.attn_flops
kv_bytes_per_token = _q3.kv_bytes_per_token
decode_attn_cost = _q3.decode_attn_cost


def held(m: dict):
    """(first id, count) of the experts this chip holds."""
    first, count = m["deployment"]["held_experts"]
    return first, count


def bank(m: dict) -> int:
    """The router's width: the published number of experts."""
    return m["published"]["num_experts"]


def validate(m: dict, harness_keys) -> None:
    """Raises ``ValueError`` for a key of ``m`` that is neither the
    harness's, mapped, fixed nor inert, for a fixed key at another value,
    and for a held range that is not the file's ``num_experts`` experts of
    the published bank."""
    known = set(harness_keys) | set(SIZES) | set(FIXED) | set(INERT)
    unknown = sorted(set(m) - known)
    if unknown:
        raise ValueError(
            f"keys not described by the qwen3_moe family: {unknown}")
    missing = sorted(set(SIZES) - set(m))
    if missing:
        raise ValueError(f"qwen3_moe configuration lacks {missing}")
    wrong = {k: m[k] for k, v in FIXED.items() if k in m and m[k] != v}
    if wrong:
        raise ValueError(
            f"qwen3_moe family describes only {FIXED}; got {wrong}")
    try:
        first, count = held(m)
        total = bank(m)
    except (KeyError, TypeError, ValueError) as e:
        raise ValueError("qwen3_moe configuration needs "
                         "deployment.held_experts [first, count] and "
                         f"published.num_experts ({e!r})") from None
    if count != m["num_experts"] or first < 0 or first + count > total:
        raise ValueError(f"held experts {first}..{first + count - 1} are "
                         f"not num_experts={m['num_experts']} of the "
                         f"published {total}")


def arch_config(m: dict):
    """The program's ``ArchConfig``: the ``moe`` family with per-head
    RMSNorm on queries and keys, the router over the published bank, and
    the held range of experts."""
    from repro.configs.base import ArchConfig
    return ArchConfig(
        name=m["model_type"], family="moe",
        num_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        d_ff=m["moe_intermediate_size"], vocab_size=m["vocab_size"],
        num_experts=bank(m), experts_per_token=m["num_experts_per_tok"],
        held_experts=held(m),
        tie_embeddings=m["tie_word_embeddings"], qk_norm=True,
        rope_theta=float(m["rope_theta"]), norm_eps=float(m["rms_norm_eps"]),
        dtype=m["torch_dtype"])


def _attn_dims(m: dict):
    hd = m["head_dim"]
    return (m["hidden_size"], hd, m["num_attention_heads"] * hd,
            m["num_key_value_heads"] * hd)


def expert_params(m: dict) -> int:
    """Parameters of the held experts of one layer."""
    return m["num_experts"] * 3 * m["hidden_size"] \
        * m["moe_intermediate_size"]


def layer_params(m: dict) -> int:
    """Parameters of one decoder layer as held here: attention, norms,
    the whole router and the held experts."""
    d, hd, q, kv = _attn_dims(m)
    return (d * q + 2 * d * kv + q * d + 2 * hd + 2 * d + d * bank(m)
            + expert_params(m))


def model_params(m: dict) -> int:
    emb = m["vocab_size"] * m["hidden_size"]
    head = 0 if m["tie_word_embeddings"] else emb
    return (m["num_hidden_layers"] * layer_params(m) + emb + head
            + m["hidden_size"])


def moe_rows(m: dict, tokens) -> float:
    """Expert rows the held experts compute for ``tokens`` tokens, on
    average: each token takes ``num_experts_per_tok`` of the bank."""
    return tokens * m["num_experts_per_tok"] * m["num_experts"] / bank(m)


def moe_cost(m: dict, rows, itemsize: int = 2):
    """(bytes, flops) of one call of one layer's held experts on ``rows``
    routed rows: every held expert's three matrices read once, each row's
    input read and output written once, and the SwiGLU's three products
    for each row."""
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    nbytes = expert_params(m) * itemsize + 2 * rows * d * itemsize
    return nbytes, 6 * rows * d * f


def _matmul_flops_per_token(m: dict) -> float:
    d, _, q, kv = _attn_dims(m)
    attn = d * q + 2 * d * kv + q * d
    router = d * bank(m)
    return 2 * m["num_hidden_layers"] * (attn + router) \
        + m["num_hidden_layers"] * moe_cost(m, moe_rows(m, 1))[1]


def _head_flops(m: dict) -> int:
    return 2 * m["hidden_size"] * m["vocab_size"]


def prefill_flops(m: dict, plen: int) -> float:
    """One prompt of ``plen`` tokens: every projection, the router and the
    routed expert rows for every token, causal attention, and the output
    head for the last position."""
    causal_keys = plen * (plen + 1) // 2
    return (plen * _matmul_flops_per_token(m) + attn_flops(m, 1) * causal_keys
            + _head_flops(m))


def decode_flops(m: dict, valid_lens) -> float:
    """One batched decode step: each lane's token through every
    projection, the router, its routed expert rows and the head, attending
    to its ``valid`` cached tokens."""
    per_tok = _matmul_flops_per_token(m) + _head_flops(m)
    return sum(per_tok + attn_flops(m, v) for v in valid_lens)
