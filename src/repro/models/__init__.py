"""Model registry: family -> module, plus uniform entry points.

Every family module exposes:
    param_template(cfg)                     -> tree of P leaves
    forward / loss_fn(cfg, params, batch)   -> training path
    init_cache / cache_spec                 -> decode state
    prefill / decode_step                   -> serving path
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeSpec
from repro.models import common


def get_module(cfg: ArchConfig):
    fam = cfg.family
    if fam in ("dense", "vlm"):
        from repro.models import transformer
        return transformer
    if fam == "moe":
        from repro.models import moe
        return moe
    if fam == "ssm":
        from repro.models import rwkv6
        return rwkv6
    if fam == "hybrid":
        from repro.models import rglru
        return rglru
    if fam == "audio":
        from repro.models import encdec
        return encdec
    if fam == "cnn":
        from repro.models import cnn
        return cnn
    raise KeyError(f"unknown family {fam!r}")


def param_template(cfg: ArchConfig):
    return get_module(cfg).param_template(cfg)


def init_params(cfg: ArchConfig, key, dtype=jnp.float32):
    return common.init_params(param_template(cfg), key, dtype)


def param_count(cfg: ArchConfig, active_only: bool = False) -> int:
    n = common.param_count_of(param_template(cfg))
    if active_only and cfg.is_moe:
        # a token uses k of the E experts, so k/E of those held here
        d, f, L, E, k = (cfg.d_model, cfg.d_ff, cfg.num_layers,
                         cfg.num_experts, cfg.experts_per_token)
        held = cfg.expert_range[1]
        n = n - L * held * 3 * d * f + L * k * held * 3 * d * f // E
    return n


def effective_window(cfg: ArchConfig, shape: ShapeSpec) -> int:
    """Window used for a given input shape (0 = full attention)."""
    if shape.name == "long_500k" and cfg.sliding_window:
        return cfg.sliding_window
    return 0


def cache_len(cfg: ArchConfig, shape: ShapeSpec) -> int:
    w = effective_window(cfg, shape)
    if cfg.family == "hybrid":
        return min(shape.seq_len, cfg.local_window)
    return min(shape.seq_len, w) if w else shape.seq_len


def input_specs(cfg: ArchConfig, shape: ShapeSpec,
                dtype=jnp.bfloat16) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins (+ logical axes) for every model input.

    Returns dict with 'args' (kwargs for the step fn) and 'axes' (matching
    logical-axis tuples) — consumed by launch.dryrun.
    """
    B, S = shape.global_batch, shape.seq_len
    tok = lambda s: jax.ShapeDtypeStruct(s, jnp.int32)
    if shape.kind == "train":
        args = {"tokens": tok((B, S)), "labels": tok((B, S))}
        axes = {"tokens": ("batch", None), "labels": ("batch", None)}
        if cfg.family == "audio":
            args["frames"] = jax.ShapeDtypeStruct(
                (B, cfg.encoder_seq, cfg.d_model), dtype)
            axes["frames"] = ("batch", None, None)
        return {"batch": args, "batch_axes": axes}
    if shape.kind == "prefill":
        args = {"tokens": tok((B, S))}
        axes = {"tokens": ("batch", None)}
        if cfg.family == "audio":
            args["frames"] = jax.ShapeDtypeStruct(
                (B, cfg.encoder_seq, cfg.d_model), dtype)
            axes["frames"] = ("batch", None, None)
        return {"batch": args, "batch_axes": axes}
    # decode: ONE new token against a cache of cache_len
    mod = get_module(cfg)
    cl = cache_len(cfg, shape)
    cache, cache_axes = mod.cache_spec(cfg, B, cl, dtype)
    return {
        "batch": {"token": tok((B, 1))},
        "batch_axes": {"token": ("batch", None)},
        "cache": cache,
        "cache_axes": cache_axes,
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }
