"""Slot-based continuous-batching decode scheduler.

The scheduler keeps ``max_slots`` decode lanes resident on the device.
All per-token state — last token, per-lane position, temperature, active
mask, PRNG key, the KV/SSM cache and the output ring — lives in one
device-side state pytree.  One jitted step advances every lane: model
decode, then on-device sampling (argmax where a lane's temperature is 0,
categorical elsewhere), then a scatter into the output buffer.  The host
loop only dispatches steps and keeps slot lifetimes it can compute
without reading device data, so a generated token costs **zero host
syncs**; the one device->host transfer per request is the fetch of its
output row at retirement.

A request is admitted mid-flight into a free lane by one jitted program,
``_admit``: a B=1 prefill of the prompt, its first token sampled on
device, and the lane's KV and fields written into the live batch while
the other lanes keep decoding.  The ring layout splices the prefilled
row into the lane's cache row.  In the paged layout
(``kv_layout='paged'``) :class:`~repro.runtime.pagepool.LanePages`
decides which pages a lane holds and returns the page-table edits as
data; the scheduler decides when, and applies them.  A prefix-cache hit
maps the cached pages and prefills only the suffix.

The decode step is lane-major (``decode_mode='batched'``): the family's
``decode_step_batch`` takes the whole (B, 1) token batch and the
per-lane positions, with the attention implementation resolved by name
through the op registry (``ref`` = jnp oracle, ``pallas`` = the decode
kernel).  ``decode_mode='vmapped'`` — the B=1 ``decode_step`` vmapped
over lanes — is the reference the batched path must match
token-for-token; families without a batch step fall back to it.

Prompt-length bucketing (``prefill_buckets``) bounds XLA compiles to a
few prompt shapes by LEFT-padding each prompt up to its bucket.  The
models apply no padding mask, so pad tokens are attended and positions
shift by the pad count; the default (``None``) prefills at exact lengths
and is bit-identical to a solo run.

Request lifecycle: when the paged pool cannot supply a page mid-decode,
the lowest-priority lane is **preempted** — its pages released, its
prompt + output-so-far requeued at the front of ``pending`` — and
re-admitted through the normal path (recompute preemption,
token-identical under greedy).  A per-lane device-side stop set lets a
lane that samples EOS clear its own ``active`` bit without a host sync;
a periodic done-mask fetch (``mask_syncs``, only when a live lane has
stop tokens) retires such lanes with ``finish_reason="eos"``.  Requests
carry optional ``deadline_s`` deadlines, ``cancel(uid)`` retires a lane
or drops a pending request, an optional
:class:`~repro.runtime.faults.FaultInjector` is consulted at page
allocation, admission and step boundaries, and a no-progress watchdog
turns a host/device desync into a diagnostic error.

Telemetry: a :class:`~repro.runtime.telemetry.MetricsRegistry` always
holds the counters, timers and TTFT / inter-token / queue / end-to-end
latency histograms; ``telemetry=Telemetry(...)`` adds the Chrome-trace
recorder of per-request lifecycle rows.  The scheduler's phases
(``telemetry.SPANS``) are always marked as ``sched.*`` profiler
annotations, so a ``jax.profiler`` trace puts them beside the device's
operations.  All instrumentation is host-clock only and measures
*dispatch*, not device completion (the zero-host-syncs-per-token
invariant survives tracing); see ``runtime/telemetry.py``.
"""
from __future__ import annotations

import math
import time
from collections import deque, namedtuple
from dataclasses import dataclass, field
from typing import Any, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import models
from repro.configs.base import ArchConfig
from repro.runtime.faults import FaultInjector
from repro.runtime.pagepool import (CopyEdit, EntryEdit, LanePages,
                                    PagePool, RowEdit)
from repro.runtime.roofline import HWSpec, RooflineAccountant
from repro.runtime.telemetry import (PID_SCHED, MetricsRegistry, Span,
                                     Telemetry)

FreeCapacity = namedtuple("FreeCapacity", ["lanes", "pages"])


@dataclass
class Request:
    uid: int
    prompt: List[int]
    max_new_tokens: int = 16
    temperature: float = 0.0
    output: List[int] = field(default_factory=list)
    done: bool = False
    submitted_at: float = 0.0
    finished_at: float = 0.0
    # extra stop tokens (union'd with the scheduler's eos_id), a
    # wall-clock deadline from submit(), and how the request ended:
    # "eos" | "length" | "cancelled" | "timeout"
    stop_tokens: Optional[List[int]] = None
    deadline_s: Optional[float] = None
    finish_reason: Optional[str] = None
    # when the admission dispatch that sampled the first token returned
    # (host clock; survives preemption so TTFT is recorded once), and
    # the scheduler snapshot attached on cancel / timeout retirement
    first_token_at: float = 0.0
    diagnostics: Optional[Dict[str, Any]] = None
    # SLO budgets: TTFT / inter-token-latency targets in seconds (None
    # = the scheduler's defaults), judged at the retirement fetch into
    # the registry's ``slo.*`` counters and the ``goodput`` fraction
    slo_ttft_s: Optional[float] = None
    slo_itl_s: Optional[float] = None


def _sample(key, logits, temp):
    """Greedy where temp == 0, categorical elsewhere — per row, on device."""
    greedy = jnp.argmax(logits, axis=-1)
    scaled = logits / jnp.maximum(temp, 1e-6)[:, None]
    sampled = jax.random.categorical(key, scaled, axis=-1)
    return jnp.where(temp > 0.0, sampled, greedy).astype(jnp.int32)


class ContinuousBatchingScheduler:
    """Continuous batching over any family exposing prefill/decode_step.

    Host-side bookkeeping (which slot serves which request, how many
    tokens it has produced) is derivable without device reads, so the
    decode loop never blocks on the device.  ``host_syncs`` counts the
    transfers that DO happen — exactly one per retired request.
    """

    def __init__(self, cfg: ArchConfig, params, *, max_slots: int = 8,
                 cache_len: int = 256, max_new_cap: int = 64,
                 pad_id: int = 0, seed: int = 0,
                 prefill_buckets: Optional[List[int]] = None,
                 decode_mode: str = "batched",
                 attn_backend: Optional[str] = None,
                 kv_dtype: Optional[str] = None,
                 kv_layout: str = "ring", page_size: int = 16,
                 num_pages: Optional[int] = None,
                 prefix_sharing: bool = True,
                 eos_id: Optional[int] = None,
                 max_stop_tokens: int = 4,
                 eos_check_interval: int = 8,
                 watchdog_ticks: int = 256,
                 faults: Optional[FaultInjector] = None,
                 telemetry: Optional[Telemetry] = None,
                 slo_ttft_s: Optional[float] = None,
                 slo_itl_s: Optional[float] = None,
                 hw: Optional[HWSpec] = None):
        self.cfg = cfg
        self.params = params
        self.mod = models.get_module(cfg)
        # telemetry: None keeps the Chrome tracer off (the sched.*
        # profiler spans are always on); the MetricsRegistry ALWAYS
        # exists — the one stats surface (see _METRIC_ATTRS below)
        self.telemetry = telemetry
        self._tracer = telemetry.tracer if telemetry is not None else None
        self.metrics = telemetry.metrics if telemetry is not None \
            else MetricsRegistry()
        if telemetry is not None:
            telemetry.tracer.ensure_thread(PID_SCHED, 0, "ticks")
        self._last_tick_s = 0.0
        self.max_slots = max_slots
        self.cache_len = cache_len
        self.max_new_cap = max_new_cap
        self.pad_id = pad_id
        self.prefill_buckets = sorted(prefill_buckets) if prefill_buckets \
            else None
        # 'vmapped' (the B=1 decode_step over lanes) is the reference the
        # lane-major 'batched' step must match token-for-token
        if decode_mode not in ("batched", "vmapped"):
            raise ValueError(f"unknown decode_mode {decode_mode!r}")
        if decode_mode == "batched" and \
                not hasattr(self.mod, "decode_step_batch"):
            decode_mode = "vmapped"
        self.decode_mode = decode_mode
        # a family whose steps count their routing (MoE) keeps the
        # counters on the device, in the state, and they reach the
        # registry at the retirement fetch: no sync of their own
        self._routing = getattr(self.mod, "ROUTING_STATS", ()) \
            if decode_mode == "batched" else ()
        self._routing_seen = np.zeros(len(self._routing), np.int64)
        # kv_dtype: None keeps an f32 cache (token-identical to the vmapped
        # reference); 'bf16' halves KV bytes; 'int8' quarters them
        if kv_dtype not in (None, "bf16", "int8"):
            raise ValueError(f"unknown kv_dtype {kv_dtype!r} "
                             "(expected None, 'bf16' or 'int8')")
        if kv_dtype == "int8" and decode_mode != "batched":
            raise ValueError(
                "kv_dtype='int8' requires decode_mode='batched' — the "
                "single-token decode_step has no quantized cache path")
        self.kv_dtype = kv_dtype
        # kv_layout='paged': a global pool of fixed-size pages behind a
        # (B, W) page table, whose host side ``self.pages`` owns (None
        # for the ring layout).  Families without ``paged_info`` (rwkv6
        # has no KV to page) fall back to the ring layout silently.
        if kv_layout not in ("ring", "paged"):
            raise ValueError(f"unknown kv_layout {kv_layout!r} "
                             "(expected 'ring' or 'paged')")
        self.page_size = page_size
        self.kv_layout = "ring"
        self.pages: Optional[LanePages] = None
        self.pool: Optional[PagePool] = None
        self.prefix_sharing = False
        # a lane's capacity in tokens, and the prefill row's length
        self._capacity = cache_len
        cache_kw = {}
        paged = kv_layout == "paged" and hasattr(self.mod, "paged_info")
        if paged:
            if decode_mode != "batched":
                raise ValueError(
                    "kv_layout='paged' requires decode_mode='batched' — "
                    "the vmapped decode_step has no paged cache path")
            info = self.mod.paged_info(cfg, cache_len, page_size)
            # the pool holds at least one whole lane, so a request that
            # passes submit's capacity check always fits it
            self.pages = LanePages(
                num_pages, page_size, max_slots,
                int(info["pages_per_lane"]), capacity=int(info["capacity"]),
                alloc_mode=info["alloc"],
                prefix_sharing=bool(info["prefix_sharing"])
                and prefix_sharing,
                metrics=self.metrics, refuse_alloc=self._alloc_refused)
            self.kv_layout = "paged"
            self.pool = self.pages.pool
            self.num_pages = self.pool.num_pages
            self.pages_per_lane = self.pages.pages_per_lane
            self.prefix_sharing = self.pages.prefix_sharing
            # whole pages: the splice reads the first n*ps ring slots
            self._capacity = self.pages.capacity
            cache_kw = {"page_size": page_size, "num_pages": self.num_pages}
        # registry name (ref|pallas|auto); the registry's backend() falls
        # back to 'ref' silently, so reject typos here where the intent
        # is explicit — a misspelled 'pallas' must not benchmark 'ref'
        if attn_backend is not None:
            from repro.core.ops import REGISTRY, resolve_decode_backend
            resolved = resolve_decode_backend(
                attn_backend, quantized=(kv_dtype == "int8"),
                paged=paged)
            known = REGISTRY.op("decode_attention").backends
            if resolved not in known:
                raise ValueError(
                    f"unknown attn_backend {attn_backend!r} "
                    f"(known: {sorted(known)} or 'auto')")
        self.attn_backend = attn_backend
        self.pending: Deque[Request] = deque()
        self.slots: List[Optional[Request]] = [None] * max_slots
        self._steps_left = np.zeros(max_slots, np.int64)
        # the counters are registry-backed properties, NOT zeroed here so
        # a shared Telemetry keeps its totals across scheduler rebuilds
        self.eos_id = eos_id
        if max_stop_tokens < 1:
            raise ValueError("max_stop_tokens must be >= 1")
        self.max_stop_tokens = max_stop_tokens
        self.eos_check_interval = max(1, eos_check_interval)
        self.watchdog_ticks = watchdog_ticks
        self.faults = faults
        if faults is not None and telemetry is not None \
                and getattr(faults, "telemetry", None) is None:
            faults.telemetry = telemetry       # injected faults leave traces
        self._tick_no = 0
        self._stall_ticks = 0
        # uids cancelled before we could find them (still pending behind
        # other requests, or mid-admission) — consumed at admission time
        self._cancel_requested: set = set()
        # which lanes have a non-empty stop set: the done-mask fetch runs
        # only when a live lane could stop early (zero syncs otherwise)
        self._has_stops = np.zeros(max_slots, bool)
        self._stop_sets: List[frozenset] = [frozenset()] * max_slots
        # SLO defaults, overridden per request; with neither budget a
        # request stays out of the goodput denominator
        self.slo_ttft_s = slo_ttft_s
        self.slo_itl_s = slo_itl_s
        self.state = self._init_state(seed, cache_kw)
        # host mirror of each lane's position (tokens in cache): the
        # pager's write position and the roofline accountant's count
        self._pos = np.zeros(max_slots, np.int64)
        # roofline accountant: analytic bytes/flops per decode token from
        # cache/param METADATA + the mirrored positions — host arithmetic
        self.roofline = RooflineAccountant(
            cfg, self.state["cache"], params, batch=max_slots,
            paged=paged, page_size=page_size,
            pages_per_lane=getattr(self, "pages_per_lane", 0), hw=hw)
        # (bytes, flops, tokens, decode_s) at the last utilization record:
        # deltas run retirement-to-retirement, the real sync points
        self._rf_anchor = (0.0, 0.0, 0, 0.0)
        # the decode step consumes the state it is given (donated): page
        # pools are updated in place, and no leaf of an old state may be
        # held across a step
        self._step_fn = jax.jit(self._step, donate_argnums=1)
        self._deactivate_fn = jax.jit(self._deactivate)
        # the one admission program of both layouts; the rest below run
        # in the paged layout only
        self._admit_fn = jax.jit(self._admit, static_argnames=("plen",))
        self._suffix_step_fn = jax.jit(self._suffix_step, donate_argnums=1)
        self._finalize_admit_fn = jax.jit(self._finalize_admit)
        self._set_pt_row_fn = jax.jit(self._set_pt_row)
        self._set_pt_entry_fn = jax.jit(self._set_pt_entry)
        self._copy_page_fn = jax.jit(self._copy_page)

    # -- device-side state and jitted programs ------------------------------

    def _init_state(self, seed: int, cache_kw) -> Dict[str, Any]:
        b, cap = self.max_slots, self.max_new_cap
        routing = {"routing": jnp.zeros((len(self._routing),), jnp.int32)} \
            if self._routing else {}
        return {**routing,
            "tokens": jnp.zeros((b, 1), jnp.int32),
            "pos": jnp.zeros((b,), jnp.int32),
            "temp": jnp.zeros((b,), jnp.float32),
            "active": jnp.zeros((b,), jnp.bool_),
            "budget": jnp.zeros((b,), jnp.int32),   # per-slot max_new_tokens
            "out_buf": jnp.full((b, cap), self.pad_id, jnp.int32),
            "out_len": jnp.zeros((b,), jnp.int32),
            # per-lane stop-token set, -1 = empty slot; a lane that
            # samples any of these clears its own active bit on device
            "stop": jnp.full((b, self.max_stop_tokens), -1, jnp.int32),
            "key": jax.random.PRNGKey(seed),
            "cache": self.mod.init_cache(self.cfg, b, self.cache_len,
                                         jnp.float32,
                                         kv_dtype=self.kv_dtype, **cache_kw),
        }

    def _decode_slots(self, params, tokens, cache, pos):
        """The family's decode_step vmapped over lanes with per-lane pos."""
        def one(p, tok, cache_row, q):
            row = jax.tree.map(lambda c: c[:, None], cache_row)
            lg, c2 = self.mod.decode_step(self.cfg, p, tok, row, q)
            return (lg.reshape(-1)[-self.cfg.vocab_size:],
                    jax.tree.map(lambda c: c[:, 0], c2))
        return jax.vmap(one, in_axes=(None, 0, 1, 0),
                        out_axes=(0, 1))(params, tokens[:, None, :],
                                         cache, pos)

    def _decode_lanes(self, params, tokens, cache, pos):
        """One decode step for every lane: the lane-major batched path
        (default) or the vmapped B=1 reference.  Returns the last logits,
        the cache and the step's routing counters (None where the family
        keeps none)."""
        if self.decode_mode == "batched":
            kw = {"with_stats": True} if self._routing else {}
            lg, cache, *stats = self.mod.decode_step_batch(
                self.cfg, params, tokens, cache, pos,
                attn_backend=self.attn_backend, **kw)
            return (lg.reshape(self.max_slots, -1,
                               self.cfg.vocab_size)[:, -1], cache,
                    stats[0] if stats else None)
        return (*self._decode_slots(params, tokens, cache, pos), None)

    def _prefill(self, params, prompt):
        """B=1 prefill of ``prompt`` into a float ring row: (logits,
        cache row, routing counters or None)."""
        kw = {"with_stats": True} if self._routing else {}
        logits, cache1, *stats = self.mod.prefill(
            self.cfg, params, prompt, self._capacity,
            cache_dtype=jnp.float32, **kw)
        return logits, cache1, stats[0] if stats else None

    def _count_routing(self, state, stats) -> Dict[str, Any]:
        """The state's routing counters after one more call's: sums, and
        a maximum last."""
        if stats is None:
            return {}
        r = state["routing"]
        return {"routing": jnp.concatenate([r[:-1] + stats[:-1],
                                            jnp.maximum(r[-1:], stats[-1:])])}

    def _step(self, params, state):
        last, cache, stats = self._decode_lanes(
            params, state["tokens"], state["cache"], state["pos"])
        with jax.named_scope("sample"):
            key, sub = jax.random.split(state["key"])
            nxt = _sample(sub, last, state["temp"])
            write = state["active"] & (state["out_len"] < state["budget"])
            rows = jnp.arange(self.max_slots)
            cols = jnp.clip(state["out_len"], 0, self.max_new_cap - 1)
            cur = state["out_buf"][rows, cols]
            out_buf = state["out_buf"].at[rows, cols].set(
                jnp.where(write, nxt, cur))
            # device-side EOS: a lane whose sampled token is in its stop
            # set clears its own active bit.  The stop token IS written to
            # the output (so "length" retirement sees it too); the lane
            # simply stops advancing.  -1 entries never match (tokens are
            # >= 0).
            stop_hit = write & (nxt[:, None] == state["stop"]).any(axis=-1)
        return {
            **state,
            "tokens": jnp.where(write[:, None], nxt[:, None],
                                state["tokens"]),
            "pos": state["pos"] + write.astype(jnp.int32),
            "active": write & ~stop_hit,
            "out_buf": out_buf,
            "out_len": state["out_len"] + write.astype(jnp.int32),
            "key": key,
            "cache": cache,
            **self._count_routing(state, stats),
        }

    def _deactivate(self, state, slot):
        """Clear one lane's active bit (cancel/timeout retirement) so its
        subsequent masked writes stay masked."""
        return {**state, "active": state["active"].at[slot].set(False)}

    def _open_lane(self, state, slot, first, plen, temp, budget, stop_row,
                   key, cache=None):
        """``state`` with lane ``slot``'s fields written: ``first`` as its
        token and output #1, at position ``plen``, with the split PRNG
        ``key`` and, where given, the ``cache`` holding its KV.  A first
        token in the stop set leaves the lane inactive."""
        hit = (first == stop_row).any()
        lane = {
            "tokens": state["tokens"].at[slot, 0].set(first),
            "pos": state["pos"].at[slot].set(plen),
            "temp": state["temp"].at[slot].set(temp),
            "active": state["active"].at[slot].set(~hit),
            "budget": state["budget"].at[slot].set(budget),
            "out_buf": state["out_buf"].at[slot].set(
                jnp.full((self.max_new_cap,), self.pad_id, jnp.int32)
                .at[0].set(first)),
            "out_len": state["out_len"].at[slot].set(1),
            "stop": state["stop"].at[slot].set(stop_row),
            "key": key,
        }
        if cache is not None:
            lane["cache"] = cache
        return {**state, **lane}

    def _admit(self, params, state, prompt, slot, temp, budget, stop_row,
               pages=None, *, plen):
        """Prefill one prompt (B=1), sample its first token on device
        (one PRNG split, from the last prefill logits), and open lane
        ``slot`` over the prompt's KV: spliced into its ring row, or into
        ``pages`` with its table row rewritten.  ``plen`` is static."""
        logits, cache1, stats = self._prefill(params, prompt)
        # quantize/cast AFTER the float prefill so admission pays the
        # conversion once, and the spliced row matches the live layout
        cache1 = self.mod.cache_to_kv_dtype(self.cfg, cache1, self.kv_dtype)
        key, sub = jax.random.split(state["key"])
        first = _sample(sub, logits[:, -1], temp[None])[0]
        if pages is None:
            cache = jax.tree.map(lambda c, c1: c.at[:, slot].set(c1[:, 0]),
                                 state["cache"], cache1)
        else:
            cache = self.mod.cache_splice_paged(self.cfg, state["cache"],
                                                cache1, slot, pages,
                                                self.page_size)
        return {**self._open_lane(state, slot, first, plen, temp, budget,
                                  stop_row, key, cache),
                **self._count_routing(state, stats)}

    # -- paged jitted programs (suffix prefill, page table updates, COW) -----

    def _suffix_step(self, params, state, tok, slot, pos_scalar):
        """One suffix-prefill step for a prefix-cache hit: feed ``tok``
        at position ``pos_scalar`` on lane ``slot`` through the regular
        batched decode (writing its KV through the page table) and
        return the lane's logits plus the state with only the cache
        advanced.

        The other lanes' writes are IDEMPOTENT: each active lane
        re-computes the KV of its current (not-yet-stepped) token at its
        current position — the identical value the next real step will
        write — and inactive lanes' zeroed table rows land in the
        garbage page.  The host runs copy-on-write checks for every
        active lane before each call, so shared pages are never touched.
        No PRNG split and no out_buf/pos mutation happens here — the
        key trajectory matches the ring scheduler exactly."""
        tokens = state["tokens"].at[slot, 0].set(tok)
        pos = state["pos"].at[slot].set(pos_scalar)
        last, cache, stats = self._decode_lanes(params, tokens,
                                                state["cache"], pos)
        return last[slot], {**state, "cache": cache,
                            **self._count_routing(state, stats)}

    def _finalize_admit(self, state, logits, slot, temp, budget, plen,
                        stop_row):
        """Close a prefix-hit admission: one PRNG split (mirroring
        :meth:`_admit`), sample the first output token from the last
        suffix-step logits, open the lane."""
        key, sub = jax.random.split(state["key"])
        first = _sample(sub, logits[None], temp[None])[0]
        return self._open_lane(state, slot, first, plen, temp, budget,
                               stop_row, key)

    def _set_pt_row(self, state, slot, row):
        cache = dict(state["cache"])
        cache["page_table"] = cache["page_table"].at[slot].set(row)
        return {**state, "cache": cache}

    def _set_pt_entry(self, state, slot, idx, pid):
        cache = dict(state["cache"])
        cache["page_table"] = cache["page_table"].at[slot, idx].set(pid)
        return {**state, "cache": cache}

    def _copy_page(self, state, src, dst, slot, idx):
        """Copy-on-write: duplicate physical page ``src`` into ``dst``
        across every pool leaf and repoint the lane's table entry."""
        cache = dict(state["cache"])
        for k in cache:
            if k.endswith("_pages"):
                cache[k] = cache[k].at[:, dst].set(cache[k][:, src])
        cache["page_table"] = cache["page_table"].at[slot, idx].set(dst)
        return {**state, "cache": cache}

    # -- telemetry plumbing --------------------------------------------------
    # Every hook below is host-only, so tracing adds no device->host
    # transfer; span ends measure dispatch, and the latency histograms
    # are anchored at the real sync points (retirement, done-mask fetch).

    def _record_routing(self, totals) -> None:
        """Fold the device's routing counters, fetched with a retirement,
        into the registry: the sums as counters (by their change since
        the last fetch, modulo the device's 32 bits), the maximum as a
        gauge."""
        totals = np.asarray(totals, np.int64)
        for name, now, seen in zip(self._routing[:-1], totals[:-1],
                                   self._routing_seen[:-1]):
            self.metrics.counter(name).inc(int((now - seen) % 2 ** 32))
        self._routing_seen = totals
        self.metrics.gauge(self._routing[-1]).set(int(totals[-1]))

    def _rt(self, uid: int):
        """The request's trace row, or None when telemetry is off."""
        return self.telemetry.request(uid) if self.telemetry is not None \
            else None

    def _record_admit(self, req: Request, slot: int, plen: int,
                      t_pop: float) -> None:
        """Queue-time + TTFT bookkeeping once a request holds a lane.
        TTFT is submit -> admission-dispatch-return (the first token is
        sampled inside the dispatched prefill program); recorded only on
        the FIRST admission so preempt/re-admit cycles don't re-count."""
        now = time.perf_counter()
        queue_s = t_pop - req.submitted_at
        self.metrics.histogram("req.queue_s").record(queue_s)
        rt = self._rt(req.uid)
        if rt is not None:
            rt.admitted(slot, plen, queue_s)
        if req.first_token_at == 0.0:
            req.first_token_at = now
            ttft = now - req.submitted_at
            self.metrics.histogram("req.ttft_s").record(ttft)
            if rt is not None:
                rt.first_token(ttft)

    def _record_finish(self, req: Request) -> None:
        """End-to-end + amortized inter-token latency at the retirement
        fetch — the one real sync point, so the ITL number is anchored
        to device completion at the far end.  One observation per
        inter-token gap (requests weight the histogram by length)."""
        self.metrics.counter(
            "sched.finish." + (req.finish_reason or "unknown")).inc()
        self.metrics.histogram("req.e2e_s").record(
            req.finished_at - req.submitted_at)
        ntot = len(req.output)
        if ntot > 1 and req.first_token_at > 0.0:
            self.metrics.histogram("req.itl_s").record(
                (req.finished_at - req.first_token_at) / (ntot - 1),
                ntot - 1)
        self._record_slo(req, ntot)
        rt = self._rt(req.uid)
        if rt is not None:
            rt.finished(req.finish_reason or "unknown", ntot)

    def _slo_budgets(self, req: Request) -> tuple:
        """Effective (ttft, itl) budgets: per-request overrides, else the
        scheduler defaults; None disables that leg."""
        ttft = req.slo_ttft_s if req.slo_ttft_s is not None \
            else self.slo_ttft_s
        itl = req.slo_itl_s if req.slo_itl_s is not None else self.slo_itl_s
        return ttft, itl

    def _record_slo(self, req: Request, ntot: int) -> None:
        """Judge SLO attainment at finish and fold it into the goodput
        fraction.  Rules: requests with neither budget stay out of the
        denominator entirely; user cancellations are excluded too (the
        caller withdrew — neither met nor missed); a deadline timeout
        counts as missed regardless of its latencies (the request did
        not complete).  TTFT/ITL use the same dispatch/retirement
        anchors as the ``req.*`` histograms."""
        if req.finish_reason == "cancelled":
            return
        ttft_budget, itl_budget = self._slo_budgets(req)
        if ttft_budget is None and itl_budget is None:
            return
        self.metrics.counter("slo.requests").inc()
        ttft = (req.first_token_at - req.submitted_at) \
            if req.first_token_at > 0.0 else math.inf
        itl = ((req.finished_at - req.first_token_at) / (ntot - 1)) \
            if ntot > 1 and req.first_token_at > 0.0 else 0.0
        met = req.finish_reason != "timeout"
        if ttft_budget is not None and ttft > ttft_budget:
            self.metrics.counter("slo.ttft_violations").inc()
            met = False
        if itl_budget is not None and itl > itl_budget:
            self.metrics.counter("slo.itl_violations").inc()
            met = False
        if met:
            self.metrics.counter("slo.met").inc()
        self.metrics.gauge("slo.goodput").set(
            self.metrics.counter("slo.met").value
            / self.metrics.counter("slo.requests").value)

    def telemetry_snapshot(self) -> Dict[str, Any]:
        """Cheap host-state snapshot for diagnostics: live-lane ages,
        free capacity, last-tick duration.  Attached to cancel/timeout
        retirements (``Request.diagnostics``) and to the no-progress
        watchdog error."""
        now = time.perf_counter()
        pool = self._pool_stats()
        return {
            "tick": self._tick_no,
            "last_tick_ms": round(self._last_tick_s * 1e3, 3),
            "lane_ages_s": {r.uid: round(now - r.submitted_at, 3)
                            for r in self.slots if r is not None},
            "pending_uids": [r.uid for r in self.pending],
            "free_lanes": sum(r is None for r in self.slots),
            **{k: pool[k] for k in ("free_pages", "pool_occupancy_frac",
                                    "prefix_hit_ratio")},
        }

    # -- host-side page bookkeeping ------------------------------------------
    # ``self.pages`` decides which page and returns the table edits; the
    # scheduler decides when, and applies them through :meth:`_apply`.

    def _apply(self, edit) -> None:
        """Write one page-table edit to the device with its program.  Each
        copies the whole pool on the device: ``sched.pt_updates``."""
        fn = {RowEdit: self._set_pt_row_fn, EntryEdit: self._set_pt_entry_fn,
              CopyEdit: self._copy_page_fn}[type(edit)]
        args = [jnp.asarray(a, jnp.int32) for a in edit]
        with Span("pt_update", self._tracer, slot=edit.slot):
            self.state = fn(self.state, *args)
        self.metrics.counter("sched.pt_updates").inc()

    def _pool_stats(self) -> Dict[str, Any]:
        """The page pool's free pages, occupancy, prefix cache and KV bytes
        resident.  The ring has no pool: its buffers are all resident."""
        cache = self.state["cache"]
        if self.pages is None:
            return {"free_pages": None, "pool_occupancy_frac": None,
                    "prefix_hit_ratio": None, "prefix_entries": 0,
                    "kv_bytes_resident": sum(int(v.size) * v.dtype.itemsize
                                             for v in cache.values())}
        return {"free_pages": self.pool.available(),
                "pool_occupancy_frac": self.pages.occupancy(),
                "prefix_hit_ratio": (self.prefix_hits / self.admissions
                                     if self.admissions else None),
                "prefix_entries": self.pool.prefix_entries(),
                "kv_bytes_resident": self.pages.resident_bytes(cache)}

    def _alloc_refused(self, site: str, slot: Optional[int], n: int) -> bool:
        """Whether the fault injector fails this page allocation (hard
        exhaustion: no eviction rescue)."""
        return self.faults is not None and self.faults.on_alloc(
            site, tick=self._tick_no, slot=slot, n=n)

    def _ensure_writable(self, slot: int, pos: int, site: str = "") -> bool:
        """Make lane ``slot`` own the page its write at ``pos`` lands in
        (first touch / copy-on-write).  False when the pool cannot
        supply it: the caller preempts a lane to free pages and
        retries."""
        edits = self.pages.writable(slot, pos, site)
        for edit in edits or ():
            self._apply(edit)
        return edits is not None

    def _prepare_writes(self, extra: Optional[int] = None) -> None:
        """Run the COW/allocation check for every lane about to write
        (except ``extra``, a lane mid suffix-prefill) before a device
        step that writes KV; 'full' allocation mode owns all pages
        up-front.  When a page cannot be supplied, the lowest-priority
        lane is preempted and the check retries — the writing lane
        itself is the last candidate."""
        if self.pages is None or self.pages.alloc_mode != "incremental":
            return
        with Span("prepare_writes", self._tracer):
            for slot in range(self.max_slots):
                if slot == extra:
                    continue
                while self.slots[slot] is not None \
                        and self._steps_left[slot] > 0 \
                        and not self._ensure_writable(
                            slot, int(self._pos[slot])):
                    victim = self._preempt_lowest(protect=extra)
                    if victim is None or victim == slot:
                        break

    def _preempt_lowest(self, protect: Optional[int] = None
                        ) -> Optional[int]:
        """Preempt the lowest-priority live lane (latest submit wins the
        axe, uid as tie-break) excluding ``protect``; returns the slot
        preempted, or None when no candidate exists."""
        victim = None
        key = None
        for slot, req in enumerate(self.slots):
            if req is None or slot == protect:
                continue
            k = (req.submitted_at, req.uid, slot)
            if key is None or k > key:
                victim, key = slot, k
        if victim is not None:
            self._preempt(victim)
        return victim

    def _preempt(self, slot: int) -> None:
        """vLLM-style recompute preemption: snapshot the lane's produced
        tokens, fold them into the prompt, release every page, and
        requeue at the FRONT of pending — re-admission recomputes the
        whole (prompt + produced) prefix through the normal
        prefill/prefix-cache path, so greedy output is token-identical
        to an uninterrupted run."""
        req = self.slots[slot]
        if int(self._steps_left[slot]) <= 0:
            # nothing left to decode — this is a retirement, not a preempt
            self._retire_slot(slot, "length")
            return
        row, n, alive = jax.device_get(
            (self.state["out_buf"][slot], self.state["out_len"][slot],
             self.state["active"][slot]))
        self.host_syncs += 1
        n = int(n)
        if not alive:
            # the lane already hit EOS on device; retire it instead of
            # recomputing a finished sequence
            self._retire_slot(slot, "eos", _prefetched=(row, n))
            return
        produced = [int(t) for t in row[:n]]
        req.output.extend(produced)
        self.tokens_generated += n
        req.prompt = list(req.prompt) + produced
        req.max_new_tokens -= n
        self.state = self._deactivate_fn(self.state, jnp.int32(slot))
        self._free_lane(slot)
        self.pending.appendleft(req)
        self.preemptions += 1
        rt = self._rt(req.uid)
        if rt is not None:
            rt.preempted(n)

    def _free_lane(self, slot: int) -> None:
        """Give lane ``slot`` back, with every page it held."""
        self.slots[slot] = None
        self._steps_left[slot] = 0
        self._pos[slot] = 0
        self._set_stop_host(slot, None)
        if self.pages is not None:
            self._apply(self.pages.release(slot))

    # -- host-side scheduling ------------------------------------------------

    def submit(self, request: Request) -> None:
        with Span("submit", self._tracer, uid=request.uid):
            request.submitted_at = time.perf_counter()
            if request.max_new_tokens > self.max_new_cap:
                raise ValueError(
                    f"request {request.uid}: max_new_tokens="
                    f"{request.max_new_tokens} exceeds scheduler cap "
                    f"{self.max_new_cap}")
            if len(self._stop_set(request)) > self.max_stop_tokens:
                raise ValueError(
                    f"request {request.uid}: {len(self._stop_set(request))} "
                    f"stop tokens exceed max_stop_tokens="
                    f"{self.max_stop_tokens}")
            plen = self._bucket(len(request.prompt))
            # a lane holds ``_capacity`` tokens (cache_len, or whole
            # pages).  The last decode step writes KV at plen + max_new - 2
            # (the final sampled token is never fed back), so beyond that
            # a request would wrap its window and corrupt its own prefix.
            # Families whose window wraps by design (rglru) or that have
            # no KV ring (rwkv6) set RING_WRAP_SAFE and skip that guard.
            cap = self._capacity
            where = (f"the {self.kv_layout} lane capacity {cap} "
                     f"(cache_len={self.cache_len})")
            if plen > cap:
                raise ValueError(
                    f"request {request.uid}: prompt length "
                    f"{len(request.prompt)} (padded to {plen} by the prefill "
                    f"bucket) exceeds {where} — the cache would wrap during "
                    "prefill and corrupt the prefix")
            if not getattr(self.mod, "RING_WRAP_SAFE", False) and \
                    plen + request.max_new_tokens - 1 > cap:
                raise ValueError(
                    f"request {request.uid}: prompt ({plen} padded) + "
                    f"max_new_tokens ({request.max_new_tokens}) would wrap "
                    f"{where} mid-decode and corrupt the prompt prefix; "
                    "shrink one of them")
            rt = self._rt(request.uid)
            if rt is not None:
                rt.submitted(len(request.prompt), request.max_new_tokens)
            self.pending.append(request)

    def _stop_set(self, req: Request) -> frozenset:
        stops = set(req.stop_tokens or ())
        if self.eos_id is not None:
            stops.add(self.eos_id)
        return frozenset(stops)

    def _stop_row(self, req: Request) -> jnp.ndarray:
        row = np.full((self.max_stop_tokens,), -1, np.int32)
        stops = sorted(self._stop_set(req))
        row[:len(stops)] = stops
        return jnp.asarray(row)

    def _set_stop_host(self, slot: int, req: Optional[Request]) -> None:
        """Mirror a lane's stop set on the host so the periodic done-mask
        fetch can be skipped entirely when no live lane could stop."""
        stops = self._stop_set(req) if req is not None else frozenset()
        self._stop_sets[slot] = stops
        self._has_stops[slot] = bool(stops)

    def _bucket(self, plen: int) -> int:
        if self.prefill_buckets is None:
            return plen
        for b in self.prefill_buckets:
            if plen <= b:
                return b
        return plen

    def _admit_pending(self) -> bool:
        t0 = time.perf_counter()
        admitted = False
        defer = False
        for slot in range(self.max_slots):
            if defer:
                break
            while not defer and self.pending \
                    and self.slots[slot] is None:
                req = self.pending.popleft()
                t_pop = time.perf_counter()
                # drop requests cancelled or expired while queued —
                # before any device work or page refs
                if req.uid in self._cancel_requested:
                    self._cancel_requested.discard(req.uid)
                    self._finish(req, "cancelled")
                    continue
                if self._deadline_expired(req):
                    self._finish(req, "timeout")
                    continue
                if self.faults is not None:
                    self.faults.on_admission(req, tick=self._tick_no,
                                             scheduler=self)
                    if req.uid in self._cancel_requested:
                        self._cancel_requested.discard(req.uid)
                        self._finish(req, "cancelled")
                        continue
                plen = self._bucket(len(req.prompt))
                toks = np.full((1, plen), self.pad_id, np.int32)
                toks[0, plen - len(req.prompt):] = req.prompt  # left-pad
                with Span("admit", self._tracer, uid=req.uid, slot=slot,
                          plen=plen):
                    verdict = self._admit_lane(req, slot, toks, plen)
                if verdict == "dropped":
                    continue                   # cancelled mid-admission
                if verdict == "defer":
                    # pool pressure: requeue and stop admitting —
                    # running lanes retire and release pages
                    if self.telemetry is not None:
                        self.telemetry.tracer.instant(
                            "admit_defer", args={"uid": req.uid})
                    self.pending.appendleft(req)
                    defer = True
                    break
                self.slots[slot] = req
                self._set_stop_host(slot, req)
                # the sampled-at-prefill first token is output token #1
                self._steps_left[slot] = req.max_new_tokens - 1
                self._pos[slot] = plen
                self._record_admit(req, slot, plen, t_pop)
                admitted = True
                break
        if admitted:
            self.prefill_s += time.perf_counter() - t0
        return admitted

    def _admit_lane(self, req: Request, slot: int, toks: np.ndarray,
                    plen: int) -> str:
        """Open lane ``slot`` for ``req``.  The ring layout has no pages
        to take.  In the paged layout a prefix-cache hit maps the cached
        pages and prefills only the suffix; a miss takes fresh pages.

        Returns ``"ok"``, ``"defer"`` (pool cannot supply the pages even
        after LRU eviction and preemption — requeue), or ``"dropped"``
        (cancelled mid-admission — request finished, do not requeue).
        Both failure paths release every ref this admission took, and
        only an admission that opens its lane is counted."""
        if self.pages is None:
            self._prefill_lane(req, slot, toks, plen)
            return "ok"
        with Span("prefix_lookup", self._tracer, tokens=plen):
            key_tokens = [int(t) for t in toks[0]]
            entry = self.pages.lookup(key_tokens)
        if entry is not None:
            verdict = self._admit_prefix_hit(req, slot, toks, plen, entry)
            if verdict != "ok":
                return verdict
        else:
            rt = self._rt(req.uid)
            if rt is not None:
                rt.prefix_lookup(False, 0)
            n = self.pages.pages_for(plen)
            with Span("alloc", self._tracer, pages=n):
                pages = self.pages.take(slot, n)
            if pages is None:
                return "defer"
            self._prefill_lane(req, slot, toks, plen, pages)
        self.admissions += 1
        self.prefill_tokens_total += plen
        if self.pages.prefix_sharing:
            with Span("prefix_register", self._tracer, tokens=plen):
                self.pages.register(slot, key_tokens)
        return "ok"

    def _prefill_lane(self, req: Request, slot: int, toks: np.ndarray,
                      plen: int, pages: Optional[np.ndarray] = None) -> None:
        """Dispatch the admission program for lane ``slot``."""
        with Span("prefill", self._tracer):
            self.state = self._admit_fn(
                self.params, self.state, jnp.asarray(toks), jnp.int32(slot),
                jnp.float32(req.temperature), jnp.int32(req.max_new_tokens),
                self._stop_row(req), pages, plen=plen)

    def _admit_prefix_hit(self, req: Request, slot: int, toks: np.ndarray,
                          plen: int, entry) -> str:
        """Map the hit's shared pages into lane ``slot``, prefill the
        rest of the prompt one batched suffix step per token, and open
        the lane from the last step's logits."""
        t, row_edit = self.pages.map_prefix(slot, entry, plen)
        rt = self._rt(req.uid)
        if rt is not None:
            rt.prefix_lookup(True, t)
        self._apply(row_edit)
        logits = None
        aborted = None
        with Span("suffix_prefill", self._tracer, uid=req.uid,
                  tokens=plen - t):
            for i in range(t, plen):
                if self.faults is not None:
                    self.faults.on_suffix_step(req, slot, i,
                                               tick=self._tick_no,
                                               scheduler=self)
                if req.uid in self._cancel_requested:
                    self._cancel_requested.discard(req.uid)
                    aborted = "dropped"
                    break
                self._prepare_writes(extra=slot)
                while not self._ensure_writable(slot, i, site="suffix:"):
                    if self._preempt_lowest(protect=slot) is None:
                        aborted = "defer"
                        break
                if aborted:
                    break
                logits, self.state = self._suffix_step_fn(
                    self.params, self.state, jnp.int32(toks[0, i]),
                    jnp.int32(slot), jnp.int32(i))
        if aborted:
            # unwind: drop every ref this lane holds (shared pages it
            # mapped AND pages the suffix loop allocated/COW'd)
            self._apply(self.pages.release(slot))
            if aborted == "dropped":
                self._finish(req, "cancelled")
            return aborted
        self.state = self._finalize_admit_fn(
            self.state, logits, jnp.int32(slot),
            jnp.float32(req.temperature),
            jnp.int32(req.max_new_tokens), jnp.int32(plen),
            self._stop_row(req))
        self.prefix_hits += 1
        self.prefill_tokens_saved += t
        return "ok"

    def _retire_slot(self, slot: int, reason: str,
                     _prefetched=None) -> None:
        """Finish the request on ``slot``: fetch its produced tokens in
        ONE device->host transfer, record its finish reason, free its
        lane (and pages), and tally the lifecycle counters."""
        req = self.slots[slot]
        with Span("retire", self._tracer, uid=req.uid, slot=slot):
            if _prefetched is not None:
                row, n = _prefetched
            else:
                # the fetch is where async dispatch settles — this span's
                # duration is real device catch-up time, not dispatch cost
                with Span("retire_fetch", self._tracer, uid=req.uid,
                          slot=slot):
                    row, n, *routing = jax.device_get(
                        (self.state["out_buf"][slot],
                         self.state["out_len"][slot],
                         *([self.state["routing"]] if self._routing
                           else [])))
                self.host_syncs += 1
                if routing:
                    self._record_routing(routing[0])
            n = int(n)
            produced = [int(t) for t in row[:n]]
            req.output.extend(produced)
            self.tokens_generated += n
            if reason == "length" and produced \
                    and produced[-1] in self._stop_sets[slot]:
                # the lane sampled EOS on its final budgeted step (or the
                # periodic mask check hadn't run yet) — the budget is spent
                # but the sequence still terminated properly
                reason = "eos"
            if reason == "eos":
                self.eos_finishes += 1
                self.eos_steps_saved += max(req.max_new_tokens - n, 0)
            elif reason in ("cancelled", "timeout"):
                # the lane may still be active on device: mask it out so its
                # writes stop before the slot is reused
                self.state = self._deactivate_fn(self.state, jnp.int32(slot))
            self._finish(req, reason)
            self._free_lane(slot)

    def _retire_finished(self) -> None:
        for slot, req in enumerate(self.slots):
            if req is None or self._steps_left[slot] > 0:
                continue
            self._retire_slot(slot, "length")

    def _finish(self, req: Request, reason: str) -> None:
        """Close ``req`` with ``reason`` and record it.  A cancelled or
        timed-out request carries the why-did-this-die snapshot, taken
        before its lane (if it holds one) is freed."""
        req.finish_reason = reason
        req.done = True
        req.finished_at = time.perf_counter()
        if reason in ("cancelled", "timeout"):
            req.diagnostics = self.telemetry_snapshot()
            if reason == "cancelled":
                self.cancellations += 1
            else:
                self.deadline_misses += 1
        self._record_finish(req)

    def cancel(self, uid: int) -> bool:
        """Cancel a request by uid.  A pending request is dropped before
        it ever touches the device; a live lane is retired immediately
        (releasing its pages).  Unknown uids are remembered and consumed
        if the request shows up later (e.g. cancel raced an admission).
        Returns True when the request was found and finished now."""
        for r in self.pending:
            if r.uid == uid:
                # identity-based removal: Request is a dataclass with
                # field equality, and two requests can be field-equal
                self.pending = deque(x for x in self.pending if x is not r)
                self._finish(r, "cancelled")
                return True
        for slot, req in enumerate(self.slots):
            if req is not None and req.uid == uid:
                self._retire_slot(slot, "cancelled")
                return True
        self._cancel_requested.add(uid)
        return False

    def _deadline_expired(self, req: Request) -> bool:
        return req.deadline_s is not None and \
            time.perf_counter() - req.submitted_at > req.deadline_s

    def _expire_deadlines(self) -> None:
        for slot, req in enumerate(self.slots):
            if req is not None and self._deadline_expired(req):
                self._retire_slot(slot, "timeout")
        expired = [r for r in self.pending if self._deadline_expired(r)]
        if expired:
            self.pending = deque(x for x in self.pending
                                 if not any(x is r for r in expired))
            for r in expired:
                self._finish(r, "timeout")

    def _reconcile_eos(self) -> None:
        """Periodic done-mask fetch: retire lanes whose device-side stop
        check already cleared their active bit.  Skipped entirely unless
        some live mid-decode lane has a non-empty stop set, so stop-free
        workloads keep strict zero host syncs per token; when it runs it
        is ONE small (B,) bool transfer per ``eos_check_interval`` ticks,
        counted in ``mask_syncs``."""
        if not any(self._has_stops[s] and self.slots[s] is not None
                   and self._steps_left[s] > 0
                   for s in range(self.max_slots)):
            return
        # this fetch is a real sync point — its span shows trace readers
        # where device completion is anchored
        with Span("eos_mask_fetch", self._tracer, tick=self._tick_no):
            alive = np.asarray(self.state["active"])
        self.mask_syncs += 1
        for slot, req in enumerate(self.slots):
            if req is not None and self._steps_left[slot] > 0 \
                    and self._has_stops[slot] and not alive[slot]:
                self._retire_slot(slot, "eos")

    def tick(self) -> bool:
        """Admit pending requests, advance every active lane one token,
        retire finished requests.  Returns False once fully idle.

        ``decode_s`` covers step dispatch AND retirement fetches — the
        fetch is where JAX's async dispatch settles, so excluding it
        would credit the scheduler with near-zero decode time."""
        self._tick_no += 1
        with Span("tick", self._tracer) as tick_span:
            busy, did = self._tick()
            # the Chrome trace keeps only the ticks that did something
            tick_span.record = any(did.values())
            tick_span.args = {"tick": self._tick_no, **did,
                              "pending": len(self.pending)}
        return busy

    def _tick(self):
        """The body of :meth:`tick`: returns whether the scheduler is
        still busy, and whether the tick admitted, worked and retired."""
        t_tick0 = time.perf_counter()
        # progress snapshot for the no-progress watchdog
        marker = (self.host_syncs, self.preemptions, self.cancellations,
                  self.deadline_misses, len(self.pending))
        if self.faults is not None:
            self.faults.on_step(self._tick_no, self)
        self._expire_deadlines()
        admitted = self._admit_pending()
        t0 = time.perf_counter()
        worked = False
        if any(self._steps_left[s] > 0
               for s, r in enumerate(self.slots) if r is not None):
            # every writing lane must own its target page before the
            # step lands (first-touch allocation / copy-on-write) —
            # this can preempt lanes, so re-check below
            self._prepare_writes()
        work = [s for s, r in enumerate(self.slots)
                if r is not None and self._steps_left[s] > 0]
        if work:
            # span/histogram measure ENQUEUE cost: the jitted step is
            # dispatched asynchronously, the device may still be running
            with Span("step_dispatch", self._tracer):
                ts0 = time.perf_counter()
                self.state = self._step_fn(self.params, self.state)
                self.metrics.histogram("sched.step_dispatch_s").record(
                    time.perf_counter() - ts0)
            # roofline accounting for the step just dispatched: host
            # arithmetic over the mirrored positions (pre-advance), no
            # device reads
            with Span("account", self._tracer):
                rf_bytes, rf_flops = self.roofline.step_cost(
                    [int(self._pos[s]) for s in work])
                self.metrics.counter("roofline.analytic_bytes").inc(rf_bytes)
                self.metrics.counter("roofline.analytic_flops").inc(rf_flops)
                self.metrics.counter("roofline.tokens").inc(len(work))
                for slot in work:
                    req = self.slots[slot]
                    self._steps_left[slot] -= 1
                    self._pos[slot] += 1
                    rt = self._rt(req.uid)
                    if rt is not None:
                        rt.progressed(req.max_new_tokens
                                      - int(self._steps_left[slot]))
            worked = True
        if worked and self._tick_no % self.eos_check_interval == 0:
            self._reconcile_eos()
        syncs = self.host_syncs
        self._retire_finished()
        retired = self.host_syncs > syncs
        if worked or retired:
            self.decode_s += time.perf_counter() - t0
        busy = bool(self.pending) or any(r is not None for r in self.slots)
        progressed = admitted or worked or marker != (
            self.host_syncs, self.preemptions, self.cancellations,
            self.deadline_misses, len(self.pending))
        if busy and not progressed:
            self._stall_ticks += 1
            if self._stall_ticks >= self.watchdog_ticks:
                self._raise_stalled()
        else:
            self._stall_ticks = 0
        with Span("account", self._tracer):
            if retired:
                # the retirement fetch is where async dispatch settles —
                # amortize achieved-vs-roofline utilization against it so
                # MBU/MFU cost no extra sync
                self._record_utilization()
            self._last_tick_s = time.perf_counter() - t_tick0
            if admitted or worked or retired:
                self.metrics.histogram("sched.tick_s").record(
                    self._last_tick_s)
            self.metrics.gauge("sched.live_lanes").set(
                sum(r is not None for r in self.slots))
            if self.pages is not None:
                free = self.pool.available()
                self.metrics.gauge("pool.free_pages").set(free)
                self.metrics.gauge("pool.occupancy_frac").set(
                    self.pages.occupancy())
                if self.admissions:
                    self.metrics.gauge("sched.prefix_hit_ratio").set(
                        self.prefix_hits / self.admissions)
                if self._tracer is not None and (admitted or worked
                                                 or retired):
                    self._tracer.counter_event("free_pages", {"free": free})
        return busy, {"admitted": admitted, "worked": worked,
                      "retired": retired}

    def _raise_stalled(self) -> None:
        lanes = [f"slot {s}: uid={r.uid} steps_left="
                 f"{int(self._steps_left[s])} pos={int(self._pos[s])}"
                 for s, r in enumerate(self.slots) if r is not None]
        snap = self.telemetry_snapshot()
        raise RuntimeError(
            f"scheduler made no progress for {self._stall_ticks} "
            f"consecutive ticks (tick {self._tick_no}): no admission, "
            f"no decode step, no retirement.  Live lanes: "
            f"{lanes or 'none'}; lane ages (s): {snap['lane_ages_s']}; "
            f"pending uids: {snap['pending_uids']}; free pages: "
            f"{snap['free_pages']}; last tick took "
            f"{snap['last_tick_ms']}ms.  "
            "This usually means host bookkeeping desynced from device "
            "state, or the pool cannot fit any pending request "
            f"(num_pages={getattr(self, 'num_pages', None)}).")

    def run(self) -> None:
        """Drive to idle: every submitted request generated and retired."""
        while self.tick():
            pass

    def free_slots(self) -> FreeCapacity:
        """Free admission capacity: open decode lanes, and allocatable
        pages in the pool (None for the ring layout: lanes only)."""
        return FreeCapacity(sum(r is None for r in self.slots),
                            self._pool_stats()["free_pages"])

    def kv_bytes_resident(self) -> int:
        """Device bytes holding KV state now: the ring's whole buffers, or
        the referenced pages plus the page table and refcounts."""
        return self._pool_stats()["kv_bytes_resident"]

    def paged_stats(self) -> Dict[str, Any]:
        """Prefix-cache / paging counters for benchmarks and tests."""
        pool = self._pool_stats()
        return {
            "layout": self.kv_layout,
            "admissions": self.admissions,
            "prefix_hits": self.prefix_hits,
            "prefix_hit_rate": (self.prefix_hits / self.admissions
                                if self.admissions else 0.0),
            "prefill_tokens_total": self.prefill_tokens_total,
            "prefill_tokens_saved": self.prefill_tokens_saved,
            "prefill_tokens_saved_frac": (
                self.prefill_tokens_saved / self.prefill_tokens_total
                if self.prefill_tokens_total else 0.0),
            "cow_copies": self.cow_copies,
            "preemptions": self.preemptions,
            "lru_evictions": self.metrics.counter("pool.evictions").value,
            **{k: pool[k] for k in ("kv_bytes_resident", "free_pages",
                                    "prefix_entries")},
        }

    def lifecycle_stats(self) -> Dict[str, Any]:
        """Request-lifecycle counters: preemption recovery, device-side
        EOS savings, deadline misses, cancellations, and the done-mask
        fetch count the EOS mirror cost."""
        return {
            "preemptions": self.preemptions,
            "eos_finishes": self.eos_finishes,
            "eos_steps_saved": self.eos_steps_saved,
            "deadline_misses": self.deadline_misses,
            "cancellations": self.cancellations,
            "mask_syncs": self.mask_syncs,
            "finish_reasons": dict(self.finish_reasons),
            "stall_ticks": self._stall_ticks,
        }

    def _record_utilization(self) -> None:
        """Fold the accounted window since the last retirement into the
        MBU/MFU instruments.  ``decode_s``'s far edge is the retirement
        fetch that just completed, so 'achieved' is anchored to device
        completion; the anchor is re-based unconditionally so a
        registry ``reset()`` (bench warmup) self-heals next window."""
        by = self.metrics.counter("roofline.analytic_bytes").value
        fl = self.metrics.counter("roofline.analytic_flops").value
        tok = self.metrics.counter("roofline.tokens").value
        dt = self.decode_s - self._rf_anchor[3]
        d_by, d_fl = by - self._rf_anchor[0], fl - self._rf_anchor[1]
        d_tok = tok - self._rf_anchor[2]
        self._rf_anchor = (by, fl, tok, self.decode_s)
        if d_tok <= 0 or dt <= 0.0:
            return
        mbu, mfu = self.roofline.utilization(d_by, d_fl, dt)
        self.metrics.histogram("roofline.mbu").record(mbu)
        self.metrics.histogram("roofline.mfu").record(mfu)
        self.metrics.gauge("roofline.mbu_last").set(mbu)
        self.metrics.gauge("roofline.mfu_last").set(mfu)
        self.metrics.gauge("roofline.bytes_per_token").set(d_by / d_tok)
        self.metrics.gauge("roofline.flops_per_token").set(d_fl / d_tok)

    def roofline_stats(self) -> Dict[str, Any]:
        """Lifetime achieved-vs-roofline summary: analytic bytes/token
        and flops/token for the tokens actually decoded, the bandwidth
        ceiling they imply on this hardware, and the achieved MBU/MFU
        over accumulated decode (dispatch + retirement-fetch) time."""
        by = self.metrics.counter("roofline.analytic_bytes").value
        fl = self.metrics.counter("roofline.analytic_flops").value
        tok = self.metrics.counter("roofline.tokens").value
        dt = self.decode_s
        bpt = by / tok if tok else 0.0
        mbu, mfu = self.roofline.utilization(by, fl, dt)
        return {
            "hw": self.roofline.describe()["hw"],
            "tokens_accounted": tok,
            "analytic_bytes_total": by,
            "analytic_flops_total": fl,
            "bytes_per_token": bpt,
            "flops_per_token": fl / tok if tok else 0.0,
            "kv_read_bytes_per_token_max": self.roofline.kv_read_bytes(
                self._capacity),
            "roofline_tok_per_s": self.roofline.roofline_tok_per_s(bpt),
            "achieved_tok_per_s": tok / dt if dt > 0 else 0.0,
            "mbu": mbu,
            "mfu": mfu,
            "decode_s": dt,
        }

    def slo_stats(self) -> Dict[str, Any]:
        """SLO attainment counters and the goodput fraction (None until
        any budgeted request finishes)."""
        n = self.metrics.counter("slo.requests").value
        met = self.metrics.counter("slo.met").value
        return {
            "slo_ttft_s": self.slo_ttft_s,
            "slo_itl_s": self.slo_itl_s,
            "requests": n,
            "met": met,
            "ttft_violations": self.metrics.counter(
                "slo.ttft_violations").value,
            "itl_violations": self.metrics.counter(
                "slo.itl_violations").value,
            "goodput": met / n if n else None,
        }

    def audit_pages(self) -> None:
        """Assert the pool-refcount invariant: every page's refcount
        equals (1 for the garbage page) + (1 per live lane mapping it)
        + (1 per prefix-cache entry spanning it).  Raises AssertionError
        on any mismatch — the refcount-leak canary the fault-injection
        suite runs after every degraded path."""
        if self.pages is not None:
            self.pages.audit([s for s, r in enumerate(self.slots)
                              if r is not None])


# -- metric-backed attributes (the single stats surface) ---------------------
# The counters live in the MetricsRegistry; these read-write properties
# over its cells make `sched.preemptions += 1`, `sched.prefill_s = 0.0`
# (bench warmup resets) and `metrics.snapshot()["sched.preemptions"]`
# all see one number.

_METRIC_ATTRS = {
    "host_syncs": "sched.host_syncs",
    "tokens_generated": "sched.tokens_generated",
    "prefill_s": "sched.prefill_s",
    "decode_s": "sched.decode_s",
    "admissions": "sched.admissions",
    "prefix_hits": "sched.prefix_hits",
    "prefill_tokens_total": "sched.prefill_tokens_total",
    "prefill_tokens_saved": "sched.prefill_tokens_saved",
    "cow_copies": "sched.cow_copies",
    "preemptions": "sched.preemptions",
    "eos_finishes": "sched.eos_finishes",
    "eos_steps_saved": "sched.eos_steps_saved",
    "deadline_misses": "sched.deadline_misses",
    "cancellations": "sched.cancellations",
    "mask_syncs": "sched.mask_syncs",
}


def _metric_attr(metric: str) -> property:
    def fget(self):
        return self.metrics.counter(metric).value

    def fset(self, v):
        self.metrics.counter(metric).value = v

    return property(fget, fset, doc=f"registry counter {metric!r}")


for _attr, _metric in _METRIC_ATTRS.items():
    setattr(ContinuousBatchingScheduler, _attr, _metric_attr(_metric))

ContinuousBatchingScheduler.finish_reasons = property(
    lambda self: self.metrics.counters_with_prefix("sched.finish."),
    doc="finish-reason tallies, reconstructed from the "
        "'sched.finish.<reason>' registry counters")
