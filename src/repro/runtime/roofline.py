"""Roofline accountant for the serving hot path.

Decode is HBM-bandwidth bound: every token streams the weights once per
batched step and each lane's live KV prefix once.  This module turns
that sentence into numbers — analytic bytes/token and flops/token —
using ONLY host-visible metadata: the cache pytree's shapes and dtypes
(never its values), the family config, and the per-lane positions the
scheduler already mirrors on host.  No call in here touches device
data, so the scheduler's zero-host-syncs-per-token invariant survives
accounting (transfer-guard tested).

The per-leaf classification is family-agnostic:

* ring slot buffers (``k``/``v`` and the int8 ``k_scale``/``v_scale``)
  cost ``per_slot_bytes × valid_len`` to read — the ragged kernel skips
  blocks beyond a lane's prefix — plus one slot written per token;
* paged pools (``*_pages``) are the same per-slot cost at page
  granularity (block-rounded through the page table, whose row is a
  ``fixed`` read);
* dense read-only state (encdec cross-attention ``xk``/``xv``) is a
  fixed per-token read;
* everything else (rglru ``h``/``conv``, rwkv6 wkv state) is recurrence
  state: read AND written every token.

The arithmetic itself lives on the ``decode_attention`` OpSpec cost
hooks (``core/ops.decode_attn_flops`` / ``decode_kv_bytes``) so the
graph cost model and the live accountant share one formula, and the
achieved-vs-roofline division uses the same hardware peaks as
``launch/dryrun`` (``launch/hlo_costs.HW_PEAKS``).
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax

from repro.core.ops import REGISTRY
from repro.launch.hlo_costs import hw_peaks, roofline_terms

__all__ = ["HWSpec", "RooflineAccountant"]


@dataclass(frozen=True)
class HWSpec:
    """Peak rates the achieved numbers are divided by.  ``detect()``
    picks the row of :data:`repro.launch.hlo_costs.HW_PEAKS` matching
    the first device's ``device_kind`` and raises for a device that is
    not in the table; on CPU the peaks are a test figure (MBU there shows
    *shape*, not magnitude).  ``REPRO_HW_PEAK_FLOPS`` /
    ``REPRO_HW_HBM_BW`` override the peaks of a KNOWN device only."""

    name: str
    peak_flops: float
    hbm_bw: float

    @classmethod
    def detect(cls) -> "HWSpec":
        row = hw_peaks(jax.devices()[0].device_kind)
        name = str(row["name"])
        env_f = os.environ.get("REPRO_HW_PEAK_FLOPS")
        env_b = os.environ.get("REPRO_HW_HBM_BW")
        if env_f is not None or env_b is not None:
            name += "+env"
        return cls(name,
                   float(env_f) if env_f is not None else row["peak_flops"],
                   float(env_b) if env_b is not None else row["hbm_bw"])


# leaves the classifier treats as ring KV slots / their int8 scales
_RING_KV = ("k", "v")
_RING_SCALE = ("k_scale", "v_scale")
_CROSS_KV = ("xk", "xv")


class RooflineAccountant:
    """Analytic per-token cost model built once per scheduler from cache
    metadata; evaluated per tick with plain host arithmetic."""

    def __init__(self, cfg, cache: Dict[str, Any], params=None, *,
                 batch: int, paged: bool = False, page_size: int = 0,
                 pages_per_lane: int = 0, block: int = 1,
                 hw: Optional[HWSpec] = None):
        self.cfg = cfg
        self.hw = hw or HWSpec.detect()
        self._spec = REGISTRY.op("decode_attention")
        heads = max(1, cfg.num_heads)
        kv = max(1, cfg.num_kv_heads)
        d = max(1, cfg.resolved_head_dim)
        # (per_slot_bytes, capacity, block) groups — one per distinct
        # slot-buffer window so rglru's short attention window and a
        # transformer's full ring coexist in one accountant
        groups: Dict[Tuple[int, int], int] = {}
        attn: Dict[Tuple[int, int], int] = {}   # (cap, block) -> layers
        self._fixed_bytes = 0.0     # read-only per token per lane
        self._state_bytes = 0.0     # recurrence: read+write per token
        self._cross_flops = 0
        for name, arr in dict(cache).items():
            nbytes = int(arr.size) * arr.dtype.itemsize
            if paged and name.endswith("_pages"):
                pool_pages = int(arr.shape[1])
                per_slot = nbytes // (pool_pages * page_size)
                key = (pages_per_lane * page_size, max(1, page_size))
                groups[key] = groups.get(key, 0) + per_slot
                if name == "k_pages":
                    attn[key] = attn.get(key, 0) + int(arr.shape[0])
            elif name == "page_table":
                self._fixed_bytes += nbytes / max(1, batch)
            elif name in _RING_KV:
                layers = int(arr.shape[0])
                slots = arr.size // (layers * batch * kv * d)
                per_slot = nbytes // (batch * slots)
                key = (int(slots), max(1, block))
                groups[key] = groups.get(key, 0) + per_slot
                if name == "k":
                    attn[key] = attn.get(key, 0) + layers
            elif name in _RING_SCALE:
                layers = int(arr.shape[0])
                slots = arr.size // (layers * batch * kv)
                per_slot = nbytes // (batch * slots)
                key = (int(slots), max(1, block))
                groups[key] = groups.get(key, 0) + per_slot
            elif name in _CROSS_KV:
                self._fixed_bytes += nbytes / max(1, batch)
                if name == "xk":
                    layers = int(arr.shape[0])
                    enc = arr.size // (layers * batch * kv * d)
                    self._cross_flops += 4 * heads * d * layers * int(enc)
            else:
                self._state_bytes += 2.0 * nbytes / max(1, batch)
        self._groups: List[Tuple[int, int, int]] = \
            [(psb, cap, blk) for (cap, blk), psb in sorted(groups.items())]
        self._attn: List[Tuple[int, int, int]] = \
            [(layers, cap, blk) for (cap, blk), layers in sorted(attn.items())]
        self._write_bytes = sum(psb for psb, _, _ in self._groups)
        self._heads, self._head_dim = heads, d
        # weight stream: the batched step reads the (active) parameters
        # once regardless of how many lanes decode; MoE routing reads
        # only the active experts, approximated by the analytic
        # active/total parameter ratio over the real leaf bytes
        if params is not None:
            pbytes = sum(int(x.size) * x.dtype.itemsize
                         for x in jax.tree.leaves(params))
        else:
            pbytes = 0
        total_p = max(1, cfg.param_count())
        active_p = cfg.active_param_count()
        self.weight_bytes_per_step = pbytes * (active_p / total_p)
        self.linear_flops_per_token = 2 * active_p

    # -- per-token closed forms (host arithmetic only) ----------------------

    def kv_read_bytes(self, valid_len: int) -> int:
        """KV-cache bytes ONE token with ``valid_len`` context reads —
        the slot-buffer term alone (no writes, no dense state), i.e. the
        quantity the ``2D/(D+4)`` bf16-over-int8 closed form predicts."""
        total = 0
        for psb, cap, blk in self._groups:
            total += self._spec.op_weight_bytes(
                {"per_slot_bytes": psb, "valid_len": valid_len,
                 "block": blk, "capacity": cap}, 0)
        return total

    def token_bytes(self, valid_len: int) -> float:
        """Total analytic HBM bytes one lane's token moves, excluding
        the per-step weight stream (amortized across lanes in
        :meth:`step_cost`): KV read + one slot written + dense reads +
        recurrence read/write."""
        return (self.kv_read_bytes(valid_len) + self._write_bytes
                + self._fixed_bytes + self._state_bytes)

    def token_flops(self, valid_len: int) -> float:
        """Analytic flops for one lane's token: ragged self-attention
        (via the ``decode_attention`` cost hook), cross-attention when
        the family has it, and the 2-flops-per-weight linear term."""
        flops = self._cross_flops + self.linear_flops_per_token
        for layers, cap, blk in self._attn:
            flops += self._spec.op_flops(
                {"num_heads": self._heads, "head_dim": self._head_dim,
                 "layers": layers, "valid_len": valid_len,
                 "block": blk, "capacity": cap}, (), ())
        return flops

    def step_cost(self, valid_lens: Sequence[int]) -> Tuple[float, float]:
        """(bytes, flops) of ONE batched decode step advancing the lanes
        with the given per-lane context lengths.  The weight stream is
        charged once per step — that is the batching win the MBU gauge
        exists to show."""
        if not len(valid_lens):
            return 0.0, 0.0
        by = self.weight_bytes_per_step
        fl = 0.0
        for v in valid_lens:
            by += self.token_bytes(int(v))
            fl += self.token_flops(int(v))
        return by, fl

    # -- achieved vs roofline ----------------------------------------------

    def utilization(self, bytes_moved: float, flops: float,
                    elapsed_s: float) -> Tuple[float, float]:
        """(MBU, MFU): achieved bytes/s and flop/s over ``elapsed_s`` as
        fractions of the hardware peaks."""
        if elapsed_s <= 0.0:
            return 0.0, 0.0
        return (bytes_moved / elapsed_s / self.hw.hbm_bw,
                flops / elapsed_s / self.hw.peak_flops)

    def roofline_tok_per_s(self, bytes_per_token: float) -> float:
        """The bandwidth-roofline decode ceiling for this cache shape:
        tokens/s if the HBM stream were the only cost."""
        if bytes_per_token <= 0.0:
            return 0.0
        return self.hw.hbm_bw / bytes_per_token

    def describe(self) -> Dict[str, Any]:
        """Static metadata for export surfaces (bench payloads, docs)."""
        return {
            "hw": {"name": self.hw.name, "peak_flops": self.hw.peak_flops,
                   "hbm_bw": self.hw.hbm_bw},
            "slot_groups": [
                {"per_slot_bytes": psb, "capacity": cap, "block": blk}
                for psb, cap, blk in self._groups],
            "fixed_bytes_per_token": self._fixed_bytes,
            "state_bytes_per_token": self._state_bytes,
            "write_bytes_per_token": self._write_bytes,
            "weight_bytes_per_step": self.weight_bytes_per_step,
            "linear_flops_per_token": self.linear_flops_per_token,
        }

    def bound(self, bytes_moved: float, flops: float) -> Dict[str, Any]:
        """Roofline decomposition of an accounted interval using the
        shared ``hlo_costs.roofline_terms`` (no collective term on the
        single-device scheduler)."""
        return roofline_terms(
            flops, bytes_moved,
            hw={"peak_flops": self.hw.peak_flops, "hbm_bw": self.hw.hbm_bw,
                "ici_bw": 1.0})
