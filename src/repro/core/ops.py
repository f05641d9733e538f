"""Op registry — the single source of truth for layer-op semantics.

Historically ``Graph`` dispatched on ``layer.kind`` with an ``if/elif``
chain copied across five methods (``out_shape``, ``apply``,
``init_params``, ``flops``, ``bytes_moved``) plus the importer's two
Caffe-type chains.  Adding an op meant editing seven places; selecting a
kernel implementation meant threading ``use_pallas``/``fft_conv``
booleans through every call site.

This module replaces all of that with one table.  Each op registers an
:class:`OpSpec` declaring

  * ``shape``       — output-shape rule,
  * ``infer``       — attr resolution from the input shape (e.g. a conv
                      discovering ``in_channels``),
  * ``init``        — parameter initialization (``None`` = no params),
  * ``flops`` / ``weight_bytes`` — analytic cost model,
  * ``inplace``     — eligibility for buffer reuse in the memory planner,
  * ``references``  — names of earlier layers the op consumes (residual
                      adds; breaks the chain-only liveness assumption),
  * ``backends``    — named implementations (``ref`` | ``pallas`` |
                      ``fft`` | ...), looked up per op at apply time,
  * ``caffe_type`` + ``to_caffe``/``from_caffe`` — the importer schema.

Registering a new op is one ``REGISTRY.register(OpSpec(...))`` call; the
graph runtime, cost model, memory planner, and JSON importer all pick it
up with no further edits.  Registering a new backend for an existing op
is ``REGISTRY.register_backend(kind, name, fn)``.

Backend functions have the uniform signature ``fn(x, params, attrs, ctx)``
where ``params`` is the layer's parameter dict (or ``None``) and ``ctx``
is an :class:`ApplyContext` carrying saved activations for ops with
``references``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Attrs = Dict[str, Any]
Shape = Tuple[int, ...]


@dataclass
class ApplyContext:
    """Per-apply state passed to backend functions: activations saved for
    later reference (residual adds) and the resolved backend map."""
    saved: Dict[str, jax.Array] = field(default_factory=dict)


@dataclass(frozen=True)
class OpSpec:
    kind: str
    shape: Callable[[Attrs, Shape], Shape]
    backends: Dict[str, Callable] = field(default_factory=dict)
    infer: Optional[Callable[[Attrs, Shape], None]] = None
    init: Optional[Callable[[jax.Array, Attrs], Dict[str, jax.Array]]] = None
    flops: Optional[Callable[[Attrs, Shape, Shape], int]] = None
    weight_bytes: Optional[Callable[[Attrs, int], int]] = None
    inplace: bool = False
    references: Optional[Callable[[Attrs], List[str]]] = None
    caffe_type: str = ""
    to_caffe: Optional[Callable[[Attrs], Dict[str, Any]]] = None
    from_caffe: Optional[Callable[[Dict[str, Any]], Attrs]] = None
    # decode the compact block-spec value used in repro.configs
    # (e.g. {"conv": [192, 5, 1, 2]} -> attrs); None = no attrs
    from_block: Optional[Callable[[Any], Attrs]] = None

    def backend(self, requested: Optional[str]) -> Callable:
        """Resolve a backend by name, falling back to ``ref`` when the op
        has no implementation under the requested name."""
        if requested and requested in self.backends:
            return self.backends[requested]
        return self.backends["ref"]

    def op_flops(self, attrs: Attrs, in_shape: Shape, out_shape: Shape) -> int:
        if self.flops is not None:
            return int(self.flops(attrs, in_shape, out_shape))
        return int(np.prod(out_shape))

    def op_weight_bytes(self, attrs: Attrs, elem: int) -> int:
        if self.weight_bytes is not None:
            return int(self.weight_bytes(attrs, elem))
        return 0


class OpRegistry:
    """kind -> OpSpec table with Caffe-type reverse lookup."""

    def __init__(self):
        self._ops: Dict[str, OpSpec] = {}

    def register(self, spec: OpSpec, *, overwrite: bool = False) -> OpSpec:
        if spec.kind in self._ops and not overwrite:
            raise ValueError(f"op {spec.kind!r} already registered")
        if "ref" not in spec.backends:
            raise ValueError(f"op {spec.kind!r} must declare a 'ref' backend")
        self._ops[spec.kind] = spec
        return spec

    def register_backend(self, kind: str, name: str, fn: Callable) -> None:
        spec = self.op(kind)
        spec.backends[name] = fn

    def op(self, kind: str) -> OpSpec:
        try:
            return self._ops[kind]
        except KeyError:
            raise KeyError(f"unknown op kind {kind!r} "
                           f"(registered: {sorted(self._ops)})") from None

    def __contains__(self, kind: str) -> bool:
        return kind in self._ops

    def kinds(self) -> List[str]:
        return sorted(self._ops)

    def by_caffe_type(self, caffe_type: str) -> OpSpec:
        for spec in self._ops.values():
            if spec.caffe_type == caffe_type:
                return spec
        raise KeyError(f"unsupported Caffe layer type {caffe_type!r}")


REGISTRY = OpRegistry()


# ---------------------------------------------------------------------------
# Reference implementations (pure jnp — the oracle / CPU path)
# ---------------------------------------------------------------------------


def conv2d_ref(x, w, b=None, *, stride: int = 1, pad: int = 0):
    """x: (B, C, H, W); w: (O, C, K, K)."""
    from jax import lax
    out = lax.conv_general_dilated(
        x, w, window_strides=(stride, stride),
        padding=[(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    if b is not None:
        out = out + b[None, :, None, None]
    return out


def pool2d_ref(x, *, mode: str = "max", kernel: int = 2, stride: int = 2,
               pad: int = 0):
    from jax import lax
    if mode == "max":
        init, op = -jnp.inf, lax.max
    else:
        init, op = 0.0, lax.add
    out = lax.reduce_window(
        x, init, op, (1, 1, kernel, kernel), (1, 1, stride, stride),
        [(0, 0), (0, 0), (pad, pad), (pad, pad)])
    if mode == "avg":
        ones = jnp.ones_like(x)
        denom = lax.reduce_window(
            ones, 0.0, lax.add, (1, 1, kernel, kernel),
            (1, 1, stride, stride),
            [(0, 0), (0, 0), (pad, pad), (pad, pad)])
        out = out / denom
    return out


def _bn_broadcast(p, ndim):
    if ndim == 4:
        return p[None, :, None, None]
    return p


def batchnorm_ref(x, p, attrs):
    """Inference-mode batch normalization with stored statistics."""
    eps = attrs.get("eps", 1e-5)
    nd = x.ndim
    inv = jax.lax.rsqrt(_bn_broadcast(p["var"], nd) + eps)
    return (x - _bn_broadcast(p["mean"], nd)) * inv \
        * _bn_broadcast(p["scale"], nd) + _bn_broadcast(p["bias"], nd)


# ---------------------------------------------------------------------------
# Shape / infer / init / cost rules
# ---------------------------------------------------------------------------


def _window_hw(h, w, k, s, p):
    return (h + 2 * p - k) // s + 1, (w + 2 * p - k) // s + 1


def _conv_shape(a, s):
    c, h, w = s
    oh, ow = _window_hw(h, w, a["kernel"], a["stride"], a["pad"])
    return (a["out_channels"], oh, ow)


def _pool_shape(a, s):
    c, h, w = s
    oh, ow = _window_hw(h, w, a["kernel"], a["stride"], a["pad"])
    return (c, oh, ow)


def _conv_init(key, a):
    fan_in = a["in_channels"] * a["kernel"] ** 2
    w = jax.random.normal(
        key, (a["out_channels"], a["in_channels"],
              a["kernel"], a["kernel"])) * math.sqrt(2 / fan_in)
    return {"w": w.astype(jnp.float32),
            "b": jnp.zeros((a["out_channels"],))}


def _dense_init(key, a):
    w = jax.random.normal(key, (a["in_features"], a["out_features"])) \
        * math.sqrt(2 / a["in_features"])
    return {"w": w.astype(jnp.float32),
            "b": jnp.zeros((a["out_features"],))}


def _batchnorm_init(key, a):
    n = a["num_features"]
    return {"scale": jnp.ones((n,)), "bias": jnp.zeros((n,)),
            "mean": jnp.zeros((n,)), "var": jnp.ones((n,))}


# ---------------------------------------------------------------------------
# Backend adapters (uniform fn(x, params, attrs, ctx) signature)
# ---------------------------------------------------------------------------


def _conv_ref_b(x, p, a, ctx):
    return conv2d_ref(x, p["w"], p["b"], stride=a["stride"], pad=a["pad"])


def _conv_pallas_b(x, p, a, ctx):
    from repro.kernels import ops as kops
    return kops.conv2d(x, p["w"], p["b"], stride=a["stride"], pad=a["pad"])


def _conv_fft_b(x, p, a, ctx):
    from repro.core.fftconv import fft_conv2d
    return fft_conv2d(x, p["w"], p["b"], stride=a["stride"], pad=a["pad"])


def _pool_ref_b(x, p, a, ctx):
    return pool2d_ref(x, mode=a["mode"], kernel=a["kernel"],
                      stride=a["stride"], pad=a["pad"])


def _pool_pallas_b(x, p, a, ctx):
    from repro.kernels import ops as kops
    return kops.pool2d(x, mode=a["mode"], kernel=a["kernel"],
                       stride=a["stride"], pad=a["pad"])


def _relu_pallas_b(x, p, a, ctx):
    from repro.kernels import ops as kops
    return kops.relu(x)


def _softmax_ref_b(x, p, a, ctx):
    return jax.nn.softmax(x.reshape(x.shape[0], -1), -1)


def _softmax_pallas_b(x, p, a, ctx):
    from repro.kernels import ops as kops
    return kops.softmax(x.reshape(x.shape[0], -1))


def _dense_ref_b(x, p, a, ctx):
    return x @ p["w"] + p["b"]


def _dense_pallas_b(x, p, a, ctx):
    from repro.kernels import ops as kops
    return kops.matmul(x, p["w"], p["b"])


def _add_b(x, p, a, ctx):
    return x + ctx.saved[a["src"]]


# ---------------------------------------------------------------------------
# Caffe interchange rules (importer schema — section 3 of the paper)
# ---------------------------------------------------------------------------

_POOL_MODES = {"MAX": "max", "AVE": "avg"}
_POOL_MODES_INV = {v: k for k, v in _POOL_MODES.items()}


def _conv_to_caffe(a):
    return {"convolution_param": {
        "num_output": a["out_channels"], "kernel_size": a["kernel"],
        "stride": a["stride"], "pad": a["pad"]}}


def _conv_from_caffe(entry):
    p = entry["convolution_param"]
    return dict(out_channels=p["num_output"], kernel=p["kernel_size"],
                stride=p.get("stride", 1), pad=p.get("pad", 0))


def _pool_to_caffe(a):
    return {"pooling_param": {
        "pool": _POOL_MODES_INV[a["mode"]], "kernel_size": a["kernel"],
        "stride": a["stride"], "pad": a["pad"]}}


def _pool_from_caffe(entry):
    p = entry["pooling_param"]
    return dict(mode=_POOL_MODES[p.get("pool", "MAX")],
                kernel=p["kernel_size"], stride=p.get("stride", 1),
                pad=p.get("pad", 0))


def _dense_to_caffe(a):
    return {"inner_product_param": {"num_output": a["out_features"]}}


def _dense_from_caffe(entry):
    return dict(out_features=entry["inner_product_param"]["num_output"])


def _bn_to_caffe(a):
    return {"batch_norm_param": {"eps": a.get("eps", 1e-5)}}


def _bn_from_caffe(entry):
    p = entry.get("batch_norm_param", {})
    return dict(eps=p.get("eps", 1e-5))


def _add_to_caffe(a):
    # Caffe expresses residual adds as an Eltwise(SUM) over two bottoms;
    # in this sequential schema the implicit bottom is the previous layer
    # and the explicit one is named here.
    return {"eltwise_param": {"operation": "SUM"}, "bottom": [a["src"]]}


def _add_from_caffe(entry):
    return dict(src=entry["bottom"][0])


# ---------------------------------------------------------------------------
# Built-in op set: the paper's Metal shader table + LeNet head + roadmap
# extensions (FFT conv backend, batchnorm, residual add)
# ---------------------------------------------------------------------------


REGISTRY.register(OpSpec(
    kind="conv",
    shape=_conv_shape,
    infer=lambda a, s: a.setdefault("in_channels", s[0]),
    init=_conv_init,
    flops=lambda a, i, o: 2 * int(np.prod(o)) * a["in_channels"]
        * a["kernel"] ** 2,
    weight_bytes=lambda a, e:
        a["out_channels"] * a["in_channels"] * a["kernel"] ** 2 * e,
    backends={"ref": _conv_ref_b, "pallas": _conv_pallas_b,
              "fft": _conv_fft_b},
    caffe_type="Convolution",
    to_caffe=_conv_to_caffe, from_caffe=_conv_from_caffe,
    from_block=lambda v: dict(zip(
        ("out_channels", "kernel", "stride", "pad"), v)),
))

REGISTRY.register(OpSpec(
    kind="pool",
    shape=_pool_shape,
    flops=lambda a, i, o: int(np.prod(o)) * a["kernel"] ** 2,
    backends={"ref": _pool_ref_b, "pallas": _pool_pallas_b},
    caffe_type="Pooling",
    to_caffe=_pool_to_caffe, from_caffe=_pool_from_caffe,
    from_block=lambda v: dict(zip(("mode", "kernel", "stride", "pad"), v)),
))

REGISTRY.register(OpSpec(
    kind="relu",
    shape=lambda a, s: s,
    inplace=True,
    backends={"ref": lambda x, p, a, ctx: jax.nn.relu(x),
              "pallas": _relu_pallas_b},
    caffe_type="ReLU",
    to_caffe=lambda a: {}, from_caffe=lambda e: {},
))

REGISTRY.register(OpSpec(
    kind="softmax",
    shape=lambda a, s: s,
    inplace=True,
    backends={"ref": _softmax_ref_b, "pallas": _softmax_pallas_b},
    caffe_type="Softmax",
    to_caffe=lambda a: {}, from_caffe=lambda e: {},
))

REGISTRY.register(OpSpec(
    kind="flatten",
    shape=lambda a, s: (int(np.prod(s)),),
    inplace=True,
    backends={"ref": lambda x, p, a, ctx: x.reshape(x.shape[0], -1)},
    caffe_type="Flatten",
    to_caffe=lambda a: {}, from_caffe=lambda e: {},
))

REGISTRY.register(OpSpec(
    kind="dense",
    shape=lambda a, s: (a["out_features"],),
    infer=lambda a, s: a.setdefault("in_features", int(np.prod(s))),
    init=_dense_init,
    flops=lambda a, i, o: 2 * a["in_features"] * a["out_features"],
    weight_bytes=lambda a, e: a["in_features"] * a["out_features"] * e,
    backends={"ref": _dense_ref_b, "pallas": _dense_pallas_b},
    caffe_type="InnerProduct",
    to_caffe=_dense_to_caffe, from_caffe=_dense_from_caffe,
    from_block=lambda v: dict(out_features=v),
))

REGISTRY.register(OpSpec(
    kind="batchnorm",
    shape=lambda a, s: s,
    infer=lambda a, s: a.setdefault("num_features", s[0]),
    init=_batchnorm_init,
    flops=lambda a, i, o: 4 * int(np.prod(o)),
    weight_bytes=lambda a, e: 4 * a["num_features"] * e,
    inplace=True,
    backends={"ref": lambda x, p, a, ctx: batchnorm_ref(x, p, a)},
    caffe_type="BatchNorm",
    to_caffe=_bn_to_caffe, from_caffe=_bn_from_caffe,
))

REGISTRY.register(OpSpec(
    kind="add",
    shape=lambda a, s: s,
    references=lambda a: [a["src"]],
    backends={"ref": _add_b},
    caffe_type="Eltwise",
    to_caffe=_add_to_caffe, from_caffe=_add_from_caffe,
    from_block=lambda v: dict(src=v),
))


# ---------------------------------------------------------------------------
# Serving hot-path ops: not graph layers, but the same named-backend
# mechanism — call sites resolve `ref` (pure-jnp oracle) vs `pallas`
# (on-chip kernel) by name instead of threading booleans.
# ---------------------------------------------------------------------------


def _decode_attn_ref_b(q, k_cache, v_cache, valid_len, *, interpret=None):
    """q: (B, 1, H, D) against a (B, KV, S, D) ring cache; valid_len
    scalar or (B,)."""
    del interpret
    from repro.models.common import attention_decode
    return attention_decode(q, k_cache, v_cache, valid_len, layout="bksd")


def _decode_attn_pallas_b(q, k_cache, v_cache, valid_len, *, interpret=None):
    from repro.kernels import ops as kops
    out = kops.decode_attention(q[:, 0], k_cache, v_cache, valid_len,
                                interpret=interpret)
    return out[:, None].astype(q.dtype)


def _decode_attn_ref_q8_b(q, k_cache, v_cache, valid_len, *, k_scale=None,
                          v_scale=None, interpret=None):
    """Int8 cache + per-slot scales: the ragged q8 jnp oracle."""
    del interpret
    from repro.kernels.ref import decode_attention_q8_ref
    out = decode_attention_q8_ref(q[:, 0], k_cache, v_cache,
                                  k_scale, v_scale, valid_len)
    return out[:, None].astype(q.dtype)


def _decode_attn_pallas_q8_b(q, k_cache, v_cache, valid_len, *,
                             k_scale=None, v_scale=None, interpret=None):
    """Int8 cache + per-slot scales: flash-decode with in-kernel dequant."""
    from repro.kernels import ops as kops
    out = kops.decode_attention_q8(q[:, 0], k_cache, v_cache,
                                   k_scale, v_scale, valid_len,
                                   interpret=interpret)
    return out[:, None].astype(q.dtype)


def _decode_attn_paged_ref_b(q, k_cache, v_cache, valid_len, *,
                             page_table=None, layer=None, interpret=None):
    """Page pool + per-lane page table: the gather-then-ring jnp oracle."""
    del interpret
    from repro.kernels.ref import decode_attention_paged_ref
    out = decode_attention_paged_ref(q[:, 0], k_cache, v_cache, page_table,
                                     valid_len, layer)
    return out[:, None].astype(q.dtype)


def _decode_attn_paged_b(q, k_cache, v_cache, valid_len, *, page_table=None,
                         layer=None, interpret=None):
    """Page pool + per-lane page table: flash-decode that copies each
    lane's valid pages through its page-table row."""
    from repro.kernels import ops as kops
    out = kops.decode_attention_paged(q[:, 0], k_cache, v_cache, page_table,
                                      valid_len, layer=layer,
                                      interpret=interpret)
    return out[:, None].astype(q.dtype)


def _decode_attn_paged_ref_q8_b(q, k_cache, v_cache, valid_len, *,
                                k_scale=None, v_scale=None, page_table=None,
                                layer=None, interpret=None):
    """Paged int8 pools + per-slot scale pools: the jnp oracle."""
    del interpret
    from repro.kernels.ref import decode_attention_paged_q8_ref
    out = decode_attention_paged_q8_ref(q[:, 0], k_cache, v_cache, k_scale,
                                        v_scale, page_table, valid_len,
                                        layer)
    return out[:, None].astype(q.dtype)


def _decode_attn_paged_q8_b(q, k_cache, v_cache, valid_len, *, k_scale=None,
                            v_scale=None, page_table=None, layer=None,
                            interpret=None):
    """Paged int8 pools: flash-decode, page-table-indirected payload and
    scale DMA + in-kernel dequant."""
    from repro.kernels import ops as kops
    out = kops.decode_attention_paged_q8(q[:, 0], k_cache, v_cache, k_scale,
                                         v_scale, page_table, valid_len,
                                         layer=layer, interpret=interpret)
    return out[:, None].astype(q.dtype)


def resolve_decode_backend(name: Optional[str], quantized: bool = False,
                           paged: bool = False) -> str:
    """``None``/'auto' -> 'pallas' on TPU (Mosaic kernel), 'ref' on the
    CPU (the interpret-mode kernel would only emulate the block
    skipping); any other backend raises rather than guess.

    ``quantized=True`` (int8 KV cache) maps the base names onto their q8
    twins — 'ref' -> 'ref_q8', 'pallas' -> 'pallas_q8'; ``paged=True``
    (page-pool KV cache) maps onto the paged twins — 'ref' ->
    'paged_ref', 'pallas' -> 'paged'.  The two compose ('paged_q8' etc.),
    so callers keep selecting implementations by the same two names
    regardless of cache dtype OR layout."""
    if name in (None, "auto"):
        backend = jax.default_backend()
        if backend not in ("tpu", "cpu"):
            raise RuntimeError(
                f"no decode-attention backend chosen for {backend!r}; "
                f"pass attn_backend='ref' or 'pallas' explicitly")
        name = "pallas" if backend == "tpu" else "ref"
    if paged and name in ("ref", "pallas"):
        name = "paged_ref" if name == "ref" else "paged"
    if quantized and name in ("ref", "pallas", "paged_ref", "paged"):
        name = name + "_q8"
    return name


def decode_attn_flops(a: Attrs, in_shape: Shape = (), out_shape: Shape = ()) -> int:
    """Analytic flops of one decode-attention token: the QK and PV dots
    are each ``valid_len x head_dim`` MACs per q-head per layer (2 flops
    per MAC), and the ragged kernel skips blocks beyond ``valid_len`` so
    the effective length is rounded up to the KV block it lands in and
    clamped to the cache capacity.  Softmax/scale flops are O(valid_len)
    and ignored.  Attrs: ``num_heads``, ``head_dim``, ``layers``,
    ``valid_len``; optional ``block`` (KV block size) and ``capacity``
    (ring slots / mapped page slots)."""
    v = _effective_slots(a)
    return 4 * a["num_heads"] * a["head_dim"] * a["layers"] * v


def decode_kv_bytes(a: Attrs, elem: int = 0) -> int:
    """Analytic HBM bytes one decode token streams from the KV cache —
    the op's "weights" in the decode roofline sense: ``per_slot_bytes``
    (sum over K/V/scale buffers of bytes per (lane, ring-slot), all
    layers) times the block-rounded valid length, plus ``fixed_bytes``
    for state read regardless of position (cross-attention K/V,
    recurrence state, page-table row).  ``elem`` is unused (the buffer
    dtypes are already folded into ``per_slot_bytes``)."""
    return a["per_slot_bytes"] * _effective_slots(a) + a.get("fixed_bytes", 0)


def _effective_slots(a: Attrs) -> int:
    """Block-rounded, capacity-clamped number of KV slots a decode step
    with ``valid_len`` tokens of context actually touches."""
    v = int(a["valid_len"])
    block = int(a.get("block", 1))
    if block > 1:
        v = -(-v // block) * block
    cap = a.get("capacity")
    if cap is not None:
        v = min(v, int(cap))
    return v


REGISTRY.register(OpSpec(
    kind="decode_attention",
    shape=lambda a, s: s,
    backends={"ref": _decode_attn_ref_b, "pallas": _decode_attn_pallas_b,
              "ref_q8": _decode_attn_ref_q8_b,
              "pallas_q8": _decode_attn_pallas_q8_b,
              "paged_ref": _decode_attn_paged_ref_b,
              "paged": _decode_attn_paged_b,
              "paged_ref_q8": _decode_attn_paged_ref_q8_b,
              "paged_q8": _decode_attn_paged_q8_b},
    flops=lambda a, i, o: decode_attn_flops(a, i, o),
    weight_bytes=lambda a, e: decode_kv_bytes(a, e),
))
