"""Slot-based continuous-batching decode scheduler.

The aligned-batch serving loop had two scaling problems the paper's
"serve many users from one GPU" story can't live with:

  * every generated token round-tripped through the host
    (``np.asarray`` per step) — a sync per token, and
  * a batch admitted together retired together: one long request held
    every slot hostage, and all requests shared one global temperature.

This scheduler keeps ``max_slots`` decode lanes resident on the device.
ALL per-token state — last token, per-slot position, per-slot
temperature, active mask, PRNG key, the KV/SSM cache, and the output
ring — lives in one device-side state pytree.  One jitted step advances
every lane: model decode, then *on-device sampling* (argmax where a
lane's temperature is 0, categorical elsewhere), then scatter into the
output buffer.  The host loop only dispatches steps and bookkeeps slot
lifetimes it can compute without reading device data, so generating a
token costs **zero host syncs**; the single device->host transfer per
request happens at retirement when its output row is fetched.

Requests are admitted mid-flight: a free slot prefill-computes the
prompt (B=1), samples the first token, and splices cache row + state
into the live batch while the other lanes keep decoding.  Per-slot
positions make this correct under rotary embeddings and ring caches.

The decode step itself is lane-major by default
(``decode_mode='batched'``): the family module's ``decode_step_batch``
takes the whole (B, 1) token batch and the per-lane position vector,
does batched QKV projections and ONE fused ragged-attention call across
all lanes — with the attention implementation resolved by name through
the op registry (``ref`` = jnp oracle, ``pallas`` = the flash-decode
kernel with per-lane block early exit).  The pre-PR-2 path — the B=1
``decode_step`` vmapped over lanes (cache batch axis 1) — survives as
``decode_mode='vmapped'``, the correctness reference the batched path
must match token-for-token; families without a batch step fall back to
it automatically.

Prompt-length bucketing (``prefill_buckets``) bounds XLA compiles to a
few prompt shapes by LEFT-padding each prompt up to its bucket.  The
models apply no padding mask, so within a bucket this reproduces the
legacy aligned loop's left-pad semantics (pad tokens are attended,
positions shift by the pad count) rather than the exact unpadded
computation — the default (``None``) prefills at exact lengths and is
bit-identical to a solo run; buckets trade that exactness for bounded
compile count, exactly as the old engine's batch-level padding did.

Request lifecycle (PR 8): the scheduler degrades instead of crashing.
When the paged pool cannot supply a page mid-decode (first touch or
copy-on-write), the lowest-priority lane is **preempted** — its pages
released, its prompt + output-so-far requeued at the front of
``pending`` — and re-admitted through the normal prefill/prefix-cache
path (vLLM-style recompute preemption), token-identical under greedy.
A per-lane device-side stop set lets a lane that samples EOS clear its
own ``active`` bit without a host sync; a periodic done-mask fetch
(``mask_syncs``, only when a live lane actually has stop tokens)
retires such lanes early with ``finish_reason="eos"``.  Requests carry
optional ``deadline_s`` wall-clock deadlines, ``cancel(uid)`` retires a
lane (or drops a pending request) releasing its pages, and an optional
:class:`~repro.runtime.faults.FaultInjector` is consulted at page
allocation, admission, and step boundaries so tests can force every
degraded path deterministically.  A no-progress watchdog turns a
host/device desync into a diagnostic error instead of a silent spin.

Telemetry (PR 9): the scheduler always owns a
:class:`~repro.runtime.telemetry.MetricsRegistry` — every counter/timer
the earlier PRs exposed ad hoc (``prefill_s``, ``paged_stats()``,
``lifecycle_stats()``) is now a view over it, plus TTFT / inter-token /
queue-time / end-to-end latency histograms recorded at each request's
lifecycle transitions.  Passing ``telemetry=Telemetry(...)`` also turns
on the Chrome-trace recorder: per-request lifecycle rows (submit →
admit → prefix hit/miss → first token → per-tick progress →
preempt/requeue → finish), exported with
``telemetry.export_chrome_trace(path)`` and viewable in Perfetto.  The
scheduler's phases (``telemetry.SPANS``: tick, admission and its parts,
page-table updates, step dispatch, bookkeeping, retirement) are always
marked as ``sched.*`` profiler annotations, so a ``jax.profiler`` trace
puts them beside the device's operations; with ``telemetry=`` they are
Chrome spans too.  All instrumentation is host-clock only and measures
*dispatch*, not device completion (the zero-host-syncs-per-token
invariant survives tracing); see ``runtime/telemetry.py`` for the exact
timestamp semantics.
"""
from __future__ import annotations

import math
import time
from collections import deque, namedtuple
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import models
from repro.configs.base import ArchConfig
from repro.runtime.faults import FaultInjector
from repro.runtime.pagepool import GARBAGE_PAGE, PagePool
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
    # lifecycle: extra per-request stop tokens (union'd with the
    # scheduler's eos_id), an optional wall-clock deadline measured from
    # submit(), and how the request ended —
    # "eos" | "length" | "cancelled" | "timeout"
    stop_tokens: Optional[List[int]] = None
    deadline_s: Optional[float] = None
    finish_reason: Optional[str] = None
    # telemetry: when the admission dispatch that sampled this request's
    # first token returned (host clock — dispatch-anchored, see
    # runtime/telemetry.py for exact semantics); survives preemption so
    # TTFT is recorded once.  ``diagnostics`` is attached on cancel /
    # timeout retirement: a scheduler-state snapshot (lane ages, free
    # pages, last-tick duration) that turns "why did this die?" into a
    # diagnosis.
    first_token_at: float = 0.0
    diagnostics: Optional[Dict[str, Any]] = None
    # SLO budgets: per-request TTFT / inter-token-latency targets in
    # seconds (None = inherit the scheduler-level defaults).  Attainment
    # is judged at the retirement fetch and rolls into the registry's
    # ``slo.*`` counters and the ``goodput`` fraction — the metric
    # chunked prefill will be judged on (ROADMAP).
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
        # telemetry: None keeps the Chrome tracer off (zero trace events,
        # and the transfer-guard tests prove zero extra device traffic
        # either way; the sched.* profiler spans are always on); the
        # MetricsRegistry ALWAYS exists — it is the one
        # stats surface behind prefill_s/decode_s, paged_stats() and
        # lifecycle_stats(), whose legacy attributes are now properties
        # over registry counters (see _METRIC_ATTRS below).
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
        # 'batched' (default): the family's lane-major decode_step_batch —
        # one fused ragged-attention call across all lanes.  'vmapped':
        # the B=1 decode_step vmapped over lanes, kept as the correctness
        # reference the batched path must match token-for-token.
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
        # kv_dtype: None keeps the legacy f32 cache (token-identical to
        # the vmapped reference); 'bf16' halves KV bytes; 'int8' quarters
        # them via the per-slot-scale quantized cache + *_q8 attention.
        if kv_dtype not in (None, "bf16", "int8"):
            raise ValueError(f"unknown kv_dtype {kv_dtype!r} "
                             "(expected None, 'bf16' or 'int8')")
        if kv_dtype == "int8" and decode_mode != "batched":
            raise ValueError(
                "kv_dtype='int8' requires decode_mode='batched' — the "
                "single-token decode_step has no quantized cache path")
        self.kv_dtype = kv_dtype
        # kv_layout='paged': block-table/paged KV — per-lane ring buffers
        # become a global pool of fixed-size pages indirected through a
        # (B, W) page table, with host-side refcounted allocation and
        # copy-on-write shared-prefix reuse.  Families that don't expose
        # ``paged_info`` (e.g. rwkv6's O(1) state has no KV to page) fall
        # back to the ring layout silently.
        if kv_layout not in ("ring", "paged"):
            raise ValueError(f"unknown kv_layout {kv_layout!r} "
                             "(expected 'ring' or 'paged')")
        self.page_size = page_size
        self._paged = False
        self.pool: Optional[PagePool] = None
        if kv_layout == "paged" and hasattr(self.mod, "paged_info"):
            if decode_mode != "batched":
                raise ValueError(
                    "kv_layout='paged' requires decode_mode='batched' — "
                    "the vmapped decode_step has no paged cache path")
            info = self.mod.paged_info(cfg, cache_len, page_size)
            self._paged = True
            self.pages_per_lane = int(info["pages_per_lane"])
            self._capacity = int(info["capacity"])
            self._alloc_mode = info["alloc"]           # incremental | full
            self.prefix_sharing = bool(info["prefix_sharing"]) and \
                prefix_sharing
            # auto pool: garbage page + a full complement per lane + one
            # lane's worth of slack for retained prefix entries
            self.num_pages = num_pages if num_pages is not None else \
                1 + (max_slots + 1) * self.pages_per_lane
            if self.num_pages < 1 + self.pages_per_lane:
                raise ValueError(
                    f"num_pages={self.num_pages} cannot hold even one "
                    f"lane ({self.pages_per_lane} pages + garbage page)")
            self.pool = PagePool(self.num_pages, page_size,
                                 metrics=self.metrics)
            # host mirrors of the device page table / lane positions —
            # kept in lockstep so allocation decisions need no device
            # reads (the zero-syncs-per-token property survives paging)
            self._pt_host = np.zeros((max_slots, self.pages_per_lane),
                                     np.int32)
            self._host_pos = np.zeros(max_slots, np.int64)
        else:
            self.prefix_sharing = False
        self.kv_layout = "paged" if self._paged else "ring"
        # prefill row length: paged capacity rounds cache_len up to whole
        # pages, and the splice reads the first n*ps ring slots
        self._prefill_len = self._capacity if self._paged else cache_len
        # registry name (ref|pallas|auto); the registry's backend() falls
        # back to 'ref' silently, so reject typos here where the intent
        # is explicit — a misspelled 'pallas' must not benchmark 'ref'
        if attn_backend is not None:
            from repro.core.ops import REGISTRY, resolve_decode_backend
            resolved = resolve_decode_backend(
                attn_backend, quantized=(kv_dtype == "int8"),
                paged=self._paged)
            known = REGISTRY.op("decode_attention").backends
            if resolved not in known:
                raise ValueError(
                    f"unknown attn_backend {attn_backend!r} "
                    f"(known: {sorted(known)} or 'auto')")
        self.attn_backend = attn_backend
        self.pending: Deque[Request] = deque()
        self.slots: List[Optional[Request]] = [None] * max_slots
        self._steps_left = np.zeros(max_slots, np.int64)
        # host_syncs / tokens_generated / prefill_s / decode_s and the
        # paged counters (admissions, prefix_hits, cow_copies, ...) are
        # registry-backed properties (see _METRIC_ATTRS at module end):
        # they read as 0 on a fresh registry and are deliberately NOT
        # zeroed here so a shared Telemetry keeps its totals across
        # ServingEngine scheduler rebuilds.
        # -- request-lifecycle state ------------------------------------
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
        # lifecycle counters (preemptions, eos_finishes, mask_syncs, ...)
        # are registry-backed properties too — see _METRIC_ATTRS
        self._tick_no = 0
        self._stall_ticks = 0
        # uids cancelled before we could find them (still pending behind
        # other requests, or mid-admission) — consumed at admission time
        self._cancel_requested: set = set()
        # host mirror of which lanes have a non-empty stop set: the
        # periodic done-mask fetch only runs when some live lane could
        # actually stop early, so stop-free workloads keep the strict
        # zero-host-syncs-per-token property
        self._has_stops = np.zeros(max_slots, bool)
        self._stop_sets: List[frozenset] = [frozenset()] * max_slots
        # SLO defaults: per-request budgets override these; None+None
        # means no request enters the goodput denominator unless it
        # carries its own budget
        self.slo_ttft_s = slo_ttft_s
        self.slo_itl_s = slo_itl_s
        self.state = self._init_state(seed)
        # roofline accountant: analytic bytes/flops per decode token
        # from cache/param METADATA + the host-mirrored lane positions —
        # pure host arithmetic, so accounting adds zero device→host
        # transfers (transfer-guard tested).  ``_host_valid`` mirrors
        # each lane's tokens-in-cache for every layout (the paged path
        # additionally keeps ``_host_pos`` for page allocation).
        self._host_valid = np.zeros(max_slots, np.int64)
        self.roofline = RooflineAccountant(
            cfg, self.state["cache"], params, batch=max_slots,
            paged=self._paged, page_size=page_size,
            pages_per_lane=getattr(self, "pages_per_lane", 0), hw=hw)
        # achieved-vs-roofline window anchor: (bytes, flops, tokens,
        # decode_s) at the last utilization record — deltas are measured
        # retirement-to-retirement because the retirement fetch is the
        # scheduler's real sync point
        self._rf_anchor = (0.0, 0.0, 0, 0.0)
        # the decode step consumes the state it is given (donated): page
        # pools are updated in place, and no leaf of an old state may be
        # held across a step
        self._step_fn = jax.jit(self._step, donate_argnums=1)
        self._deactivate_fn = jax.jit(self._deactivate)
        self._admit_fn = jax.jit(self._admit, static_argnames=("plen",))
        if self._paged:
            self._admit_paged_fn = jax.jit(self._admit_paged,
                                           static_argnames=("plen",))
            self._suffix_step_fn = jax.jit(self._suffix_step,
                                           donate_argnums=1)
            self._finalize_admit_fn = jax.jit(self._finalize_admit)
            self._set_pt_row_fn = jax.jit(self._set_pt_row)
            self._set_pt_entry_fn = jax.jit(self._set_pt_entry)
            self._copy_page_fn = jax.jit(self._copy_page)

    # -- device-side state and jitted programs ------------------------------

    def _init_state(self, seed: int) -> Dict[str, Any]:
        b, cap = self.max_slots, self.max_new_cap
        cache_kw = {"kv_dtype": self.kv_dtype}
        if self._paged:
            cache_kw.update(page_size=self.page_size,
                            num_pages=self.num_pages)
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
                                         jnp.float32, **cache_kw),
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
            self.cfg, params, prompt, self._prefill_len,
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

    def _admit(self, params, state, prompt, slot, temp, budget, stop_row,
               *, plen):
        """Prefill one prompt (B=1), sample its first token on device, and
        splice cache row + lane state into the live batch."""
        del plen  # static: selects the compiled specialization
        logits, cache1, stats = self._prefill(params, prompt)
        # quantize/cast AFTER the float prefill so admission pays the
        # conversion once, and the spliced row matches the live layout
        cache1 = self.mod.cache_to_kv_dtype(self.cfg, cache1, self.kv_dtype)
        key, sub = jax.random.split(state["key"])
        first = _sample(sub, logits[:, -1], temp[None])[0]
        cache = jax.tree.map(lambda c, c1: c.at[:, slot].set(c1[:, 0]),
                             state["cache"], cache1)
        cap = self.max_new_cap
        # the first sampled token can itself be a stop token
        hit = (first == stop_row).any()
        return {
            **state,
            "tokens": state["tokens"].at[slot, 0].set(first),
            "pos": state["pos"].at[slot].set(prompt.shape[1]),
            "temp": state["temp"].at[slot].set(temp),
            "active": state["active"].at[slot].set(~hit),
            "budget": state["budget"].at[slot].set(budget),
            "out_buf": state["out_buf"].at[slot].set(
                jnp.full((cap,), self.pad_id, jnp.int32)
                .at[0].set(first)),
            "out_len": state["out_len"].at[slot].set(1),
            "stop": state["stop"].at[slot].set(stop_row),
            "key": key,
            "cache": cache,
            **self._count_routing(state, stats),
        }

    # -- paged jitted programs (page table updates, COW, admission) ----------

    def _admit_paged(self, params, state, prompt, slot, temp, budget,
                     pages, stop_row, *, plen):
        """Paged cold-path admission: prefill the full prompt (B=1 ring
        row), scatter its KV blocks into the lane's freshly allocated
        ``pages``, rewrite the lane's table row, and splice lane state.
        Same PRNG discipline as :meth:`_admit` (one split, first token
        sampled from the last prefill logits)."""
        del plen  # static: selects the compiled specialization
        logits, cache1, stats = self._prefill(params, prompt)
        cache1 = self.mod.cache_to_kv_dtype(self.cfg, cache1, self.kv_dtype)
        key, sub = jax.random.split(state["key"])
        first = _sample(sub, logits[:, -1], temp[None])[0]
        cache = self.mod.cache_splice_paged(self.cfg, state["cache"],
                                            cache1, slot, pages,
                                            self.page_size)
        cap = self.max_new_cap
        hit = (first == stop_row).any()
        return {
            **state,
            "tokens": state["tokens"].at[slot, 0].set(first),
            "pos": state["pos"].at[slot].set(prompt.shape[1]),
            "temp": state["temp"].at[slot].set(temp),
            "active": state["active"].at[slot].set(~hit),
            "budget": state["budget"].at[slot].set(budget),
            "out_buf": state["out_buf"].at[slot].set(
                jnp.full((cap,), self.pad_id, jnp.int32)
                .at[0].set(first)),
            "out_len": state["out_len"].at[slot].set(1),
            "stop": state["stop"].at[slot].set(stop_row),
            "key": key,
            "cache": cache,
            **self._count_routing(state, stats),
        }

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
        suffix-step logits, splice lane scalars."""
        key, sub = jax.random.split(state["key"])
        first = _sample(sub, logits[None], temp[None])[0]
        cap = self.max_new_cap
        hit = (first == stop_row).any()
        return {
            **state,
            "tokens": state["tokens"].at[slot, 0].set(first),
            "pos": state["pos"].at[slot].set(plen),
            "temp": state["temp"].at[slot].set(temp),
            "active": state["active"].at[slot].set(~hit),
            "budget": state["budget"].at[slot].set(budget),
            "out_buf": state["out_buf"].at[slot].set(
                jnp.full((cap,), self.pad_id, jnp.int32)
                .at[0].set(first)),
            "out_len": state["out_len"].at[slot].set(1),
            "stop": state["stop"].at[slot].set(stop_row),
            "key": key,
        }

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
    # Every hook below is host-only (time.perf_counter + dict appends):
    # telemetry can never add a device->host transfer, so the
    # zero-host-syncs-per-token invariant holds with tracing on.  What
    # each timestamp MEANS under async dispatch is documented in
    # runtime/telemetry.py and docs/serving.md — in short, span ends
    # measure dispatch, and the per-token latency histograms are
    # anchored at the real sync points (retirement fetch, done-mask
    # fetch).

    def _pt_update(self, slot: int, fn, *args) -> None:
        """Dispatch one page-table update (``fn`` one of the
        ``_set_pt_*``/``_copy_page`` programs) for lane ``slot``.  Each
        returns a new state, so it copies the whole pool on the device:
        ``sched.pt_updates`` counts them."""
        with Span("pt_update", self._tracer, slot=slot):
            self.state = fn(self.state, *args)
        self.metrics.counter("sched.pt_updates").inc()

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
        self._host_valid[slot] = plen     # roofline: tokens in cache
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
        return {
            "tick": self._tick_no,
            "last_tick_ms": round(self._last_tick_s * 1e3, 3),
            "lane_ages_s": {r.uid: round(now - r.submitted_at, 3)
                            for r in self.slots if r is not None},
            "pending_uids": [r.uid for r in self.pending],
            "free_lanes": sum(r is None for r in self.slots),
            "free_pages": self.pool.available() if self._paged else None,
            "pool_occupancy_frac": (
                1.0 - self.pool.available() / self.num_pages
                if self._paged else None),
            "prefix_hit_ratio": (
                self.prefix_hits / self.admissions
                if self._paged and self.admissions else None),
        }

    # -- host-side page bookkeeping ------------------------------------------

    def _alloc_pages(self, n: int, *, site: str = "",
                     slot: Optional[int] = None) -> Optional[List[int]]:
        """Claim ``n`` pages, evicting LRU prefix-cache entries under
        pressure; None when the pool genuinely cannot supply them.  The
        fault injector is consulted FIRST so an injected failure models
        hard exhaustion (no eviction rescue) deterministically."""
        if self.faults is not None and self.faults.on_alloc(
                site, tick=self._tick_no, slot=slot, n=n):
            self.metrics.counter("faults.alloc_failures").inc()
            return None
        pages = self.pool.alloc(n)
        while pages is None and self.pool.evict_one():
            pages = self.pool.alloc(n)
        return pages

    def _ensure_writable(self, slot: int, pos: int, site: str = "") -> bool:
        """Guarantee lane ``slot`` exclusively owns the page its write at
        ``pos`` lands in: allocate on first touch, copy-on-write when the
        page is shared (prefix reuse keeps refcount > 1).  Invariant:
        every non-garbage entry in a lane's table row holds exactly one
        refcount on behalf of that lane.

        Returns False — WITHOUT raising — when the pool cannot supply
        the page even after LRU eviction; the caller preempts a lane to
        free pages and retries."""
        idx = (pos % self._capacity) // self.page_size
        phys = int(self._pt_host[slot, idx])
        if phys == GARBAGE_PAGE:
            got = self._alloc_pages(1, site=site + "first_touch", slot=slot)
            if got is None:
                return False
            self._pt_host[slot, idx] = got[0]
            self._pt_update(slot, self._set_pt_entry_fn, jnp.int32(slot),
                            jnp.int32(idx), jnp.int32(got[0]))
        elif self.pool.refcount[phys] > 1:
            got = self._alloc_pages(1, site=site + "cow", slot=slot)
            if got is None:
                return False
            self._pt_host[slot, idx] = got[0]
            self._pt_update(slot, self._copy_page_fn, jnp.int32(phys),
                            jnp.int32(got[0]), jnp.int32(slot),
                            jnp.int32(idx))
            self.pool.free(phys)               # drop the lane's shared ref
            self.cow_copies += 1
        return True

    def _prepare_writes(self, extra: Optional[int] = None) -> None:
        """Run the COW/allocation check for every lane about to write —
        all active lanes with steps left, plus ``extra`` (a lane mid
        suffix-prefill).  Called before every device step that writes
        KV; 'full' allocation mode owns all pages up-front so only
        incremental mode does work here.

        When a page cannot be supplied, the lowest-priority lane is
        preempted (releasing its pages) and the check retries — the
        writing lane itself is the last candidate, in which case it is
        preempted instead of written."""
        if self._alloc_mode != "incremental":
            return
        with Span("prepare_writes", self._tracer):
            for slot in range(self.max_slots):
                if slot == extra:
                    continue
                while self.slots[slot] is not None \
                        and self._steps_left[slot] > 0 \
                        and not self._ensure_writable(
                            slot, int(self._host_pos[slot])):
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
        self.slots[slot] = None
        self._steps_left[slot] = 0
        self._host_valid[slot] = 0
        self._set_stop_host(slot, None)
        self.state = self._deactivate_fn(self.state, jnp.int32(slot))
        if self._paged:
            self._release_lane_pages(slot)
        self.pending.appendleft(req)
        self.preemptions += 1
        rt = self._rt(req.uid)
        if rt is not None:
            rt.preempted(n)

    def _release_lane_pages(self, slot: int) -> None:
        """Drop the lane's reference on every page in its table row and
        zero the row on host AND device — a retired lane's stale mapping
        must never alias a reallocated page."""
        for idx in range(self.pages_per_lane):
            phys = int(self._pt_host[slot, idx])
            if phys != GARBAGE_PAGE:
                self.pool.free(phys)
        self._pt_host[slot] = 0
        self._host_pos[slot] = 0
        self._pt_update(slot, self._set_pt_row_fn, jnp.int32(slot),
                        jnp.zeros((self.pages_per_lane,), jnp.int32))

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
            # the last decode step writes KV at position plen + max_new - 2
            # (the final sampled token is never fed back), so any request
            # with plen + max_new_tokens - 1 > window would wrap the cache
            # mid-decode and corrupt its own prefix.  Families whose window
            # wraps by design (rglru's local attention) or that have no KV
            # ring at all (rwkv6) set RING_WRAP_SAFE and skip the guard.
            wrap_safe = getattr(self.mod, "RING_WRAP_SAFE", False)
            if self._paged:
                # pool-capacity guard (the old cache_len bound is obsolete:
                # a lane's logical window wraps at pages_per_lane * page_size
                # like the ring did, but pages must EXIST in the pool)
                if plen > self._capacity:
                    raise ValueError(
                        f"request {request.uid}: prompt length "
                        f"{len(request.prompt)} (padded to {plen}) exceeds "
                        f"the paged lane capacity {self._capacity} "
                        f"({self.pages_per_lane} pages x {self.page_size})")
                if not wrap_safe and \
                        plen + request.max_new_tokens - 1 > self._capacity:
                    raise ValueError(
                        f"request {request.uid}: prompt ({plen} padded) + "
                        f"max_new_tokens ({request.max_new_tokens}) would "
                        f"wrap the paged window ({self._capacity}) mid-decode "
                        "and corrupt the prompt prefix; shrink one of them")
                need = min(-(-(plen + request.max_new_tokens)
                             // self.page_size), self.pages_per_lane)
                if need > self.num_pages - 1:
                    raise ValueError(
                        f"request {request.uid}: needs {need} pages but the "
                        f"pool holds only {self.num_pages - 1} allocatable "
                        f"(num_pages={self.num_pages} incl. garbage page)")
            elif plen > self.cache_len:
                raise ValueError(
                    f"request {request.uid}: prompt length "
                    f"{len(request.prompt)} (padded to {plen} by the prefill "
                    f"bucket) exceeds cache_len={self.cache_len} — the ring "
                    f"cache would wrap during prefill and corrupt the prefix")
            elif not wrap_safe and \
                    plen + request.max_new_tokens - 1 > self.cache_len:
                raise ValueError(
                    f"request {request.uid}: prompt ({plen} padded) + "
                    f"max_new_tokens ({request.max_new_tokens}) would wrap "
                    f"the ring cache (cache_len={self.cache_len}) mid-decode "
                    "and corrupt the prompt prefix; shrink one of them")
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
                    self._finish_dropped(req, "cancelled")
                    continue
                if self._deadline_expired(req):
                    self._finish_dropped(req, "timeout")
                    continue
                if self.faults is not None:
                    self.faults.on_admission(req, tick=self._tick_no,
                                             scheduler=self)
                    if req.uid in self._cancel_requested:
                        self._cancel_requested.discard(req.uid)
                        self._finish_dropped(req, "cancelled")
                        continue
                plen = self._bucket(len(req.prompt))
                toks = np.full((1, plen), self.pad_id, np.int32)
                toks[0, plen - len(req.prompt):] = req.prompt  # left-pad
                with Span("admit", self._tracer, uid=req.uid, slot=slot,
                                plen=plen):
                    if self._paged:
                        verdict = self._admit_paged_host(req, slot, toks,
                                                         plen)
                    else:
                        verdict = "ok"
                        with Span("prefill", self._tracer):
                            self.state = self._admit_fn(
                                self.params, self.state, jnp.asarray(toks),
                                jnp.int32(slot),
                                jnp.float32(req.temperature),
                                jnp.int32(req.max_new_tokens),
                                self._stop_row(req), plen=plen)
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
                self._record_admit(req, slot, plen, t_pop)
                admitted = True
                break
        if admitted:
            self.prefill_s += time.perf_counter() - t0
        return admitted

    def _admit_paged_host(self, req: Request, slot: int, toks: np.ndarray,
                          plen: int) -> str:
        """Paged admission: prefix-cache lookup first (map shared pages
        read-only and prefill only the suffix), else allocate pages and
        run the full prefill + splice.

        Returns ``"ok"``, ``"defer"`` (pool cannot supply the pages even
        after LRU eviction and preemption — requeue), or ``"dropped"``
        (cancelled mid-admission — request finished, do not requeue).
        Both failure paths fully unwind: every ref this admission took
        is released and the counters roll back, so an aborted prefix-hit
        leaks nothing."""
        ps = self.page_size
        npages = self.pages_per_lane if self._alloc_mode == "full" \
            else -(-plen // ps)
        self.admissions += 1
        self.prefill_tokens_total += plen
        with Span("prefix_lookup", self._tracer, tokens=plen):
            key_tokens = [int(t) for t in toks[0]]
            entry = self.pool.prefix_lookup(key_tokens) \
                if self.prefix_sharing else None
        if entry is not None:
            # cap the reused length at plen - 1 so at least one suffix
            # step runs — its logits seed the first sampled token
            t = min(entry.length, plen - 1)
            nshared = -(-t // ps)
            shared = list(entry.pages[:nshared])
            self.prefix_hits += 1
            self.prefill_tokens_saved += t
            rt = self._rt(req.uid)
            if rt is not None:
                rt.prefix_lookup(True, t)
            for p in shared:
                self.pool.ref(p)
            self._pt_host[slot] = 0
            self._pt_host[slot, :nshared] = shared
            row = np.zeros((self.pages_per_lane,), np.int32)
            row[:nshared] = shared
            self._pt_update(slot, self._set_pt_row_fn, jnp.int32(slot),
                            jnp.asarray(row))
            # suffix prefill: one batched step per remaining prompt token
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
                    while not self._ensure_writable(slot, i,
                                                    site="suffix:"):
                        if self._preempt_lowest(protect=slot) is None:
                            aborted = "defer"
                            break
                    if aborted:
                        break
                    logits, self.state = self._suffix_step_fn(
                        self.params, self.state, jnp.int32(toks[0, i]),
                        jnp.int32(slot), jnp.int32(i))
            if aborted:
                # unwind: drop every ref this lane holds (shared pages
                # it mapped AND pages the suffix loop allocated/COW'd)
                # and roll the admission counters back
                self._release_lane_pages(slot)
                self.admissions -= 1
                self.prefix_hits -= 1
                self.prefill_tokens_total -= plen
                self.prefill_tokens_saved -= t
                if aborted == "dropped":
                    self._finish_dropped(req, "cancelled")
                return aborted
            self.state = self._finalize_admit_fn(
                self.state, logits, jnp.int32(slot),
                jnp.float32(req.temperature),
                jnp.int32(req.max_new_tokens), jnp.int32(plen),
                self._stop_row(req))
        else:
            rt = self._rt(req.uid)
            if rt is not None:
                rt.prefix_lookup(False, 0)
            with Span("alloc", self._tracer, pages=npages):
                pages = self._alloc_pages(npages, site="admission",
                                          slot=slot)
            if pages is None:
                self.admissions -= 1
                self.prefill_tokens_total -= plen
                return "defer"
            self._pt_host[slot] = 0
            self._pt_host[slot, :npages] = pages
            with Span("prefill", self._tracer):
                self.state = self._admit_paged_fn(
                    self.params, self.state, jnp.asarray(toks),
                    jnp.int32(slot), jnp.float32(req.temperature),
                    jnp.int32(req.max_new_tokens),
                    jnp.asarray(pages, jnp.int32), self._stop_row(req),
                    plen=plen)
        self._host_pos[slot] = plen
        if self.prefix_sharing:
            # publish this lane's page-aligned prefixes (and the full
            # prompt).  COW keeps the entries pristine once the lane
            # decodes past them.
            span_full = -(-plen // ps)
            with Span("prefix_register", self._tracer, tokens=plen):
                self.pool.prefix_register(
                    key_tokens,
                    [int(p) for p in self._pt_host[slot, :span_full]])
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
            elif reason == "cancelled":
                self.cancellations += 1
            elif reason == "timeout":
                self.deadline_misses += 1
            if reason in ("cancelled", "timeout"):
                # the lane may still be active on device: mask it out so its
                # writes stop before the slot is reused
                self.state = self._deactivate_fn(self.state, jnp.int32(slot))
            req.finish_reason = reason
            req.done = True
            req.finished_at = time.perf_counter()
            if reason in ("cancelled", "timeout"):
                # attach the why-did-this-die snapshot before the lane state
                # is torn down (satellite: "stuck" becomes a diagnosis)
                req.diagnostics = self.telemetry_snapshot()
            self._record_finish(req)
            self.slots[slot] = None
            self._steps_left[slot] = 0
            self._host_valid[slot] = 0
            self._set_stop_host(slot, None)
            if self._paged:
                self._release_lane_pages(slot)

    def _retire_finished(self) -> None:
        for slot, req in enumerate(self.slots):
            if req is None or self._steps_left[slot] > 0:
                continue
            self._retire_slot(slot, "length")

    def _finish_dropped(self, req: Request, reason: str) -> None:
        """Finish a request that never reached a lane (cancelled or
        expired while pending) — no device state to unwind."""
        req.finish_reason = reason
        req.done = True
        req.finished_at = time.perf_counter()
        req.diagnostics = self.telemetry_snapshot()
        self._record_finish(req)
        if reason == "cancelled":
            self.cancellations += 1
        elif reason == "timeout":
            self.deadline_misses += 1

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
                self._finish_dropped(r, "cancelled")
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
                self._finish_dropped(r, "timeout")

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
        if self._tracer is not None and tick_span.record and self._paged:
            self._tracer.counter_event("free_pages",
                                       {"free": self.pool.available()})
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
        if self._paged and any(self._steps_left[s] > 0
                               for s, r in enumerate(self.slots)
                               if r is not None):
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
                    [int(self._host_valid[s]) for s in work])
                self.metrics.counter("roofline.analytic_bytes").inc(rf_bytes)
                self.metrics.counter("roofline.analytic_flops").inc(rf_flops)
                self.metrics.counter("roofline.tokens").inc(len(work))
                for slot in work:
                    req = self.slots[slot]
                    self._steps_left[slot] -= 1
                    self._host_valid[slot] += 1
                    if self._paged:
                        self._host_pos[slot] += 1
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
            if self._paged:
                self.metrics.gauge("pool.free_pages").set(
                    self.pool.available())
                self.metrics.gauge("pool.occupancy_frac").set(
                    1.0 - self.pool.available() / self.num_pages)
                if self.admissions:
                    self.metrics.gauge("sched.prefix_hit_ratio").set(
                        self.prefix_hits / self.admissions)
        return busy, {"admitted": admitted, "worked": worked,
                      "retired": retired}

    def _raise_stalled(self) -> None:
        lanes = [f"slot {s}: uid={r.uid} steps_left="
                 f"{int(self._steps_left[s])}"
                 + (f" pos={int(self._host_pos[s])}" if self._paged else "")
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
        """Free admission capacity: open decode lanes, and (paged layout
        only) allocatable pages in the pool — ``pages`` is None for the
        ring layout, where lanes are the only resource."""
        lanes = sum(r is None for r in self.slots)
        pages = self.pool.available() if self._paged else None
        return FreeCapacity(lanes, pages)

    def kv_bytes_resident(self) -> int:
        """Device bytes actually holding KV state right now.  Ring: the
        full per-lane buffers (allocated whether or not a lane is live).
        Paged: only the referenced pages, plus the page-table and
        refcount bookkeeping arrays — the number the ISSUE's residency
        claim is measured on."""
        cache = self.state["cache"]
        if not self._paged:
            return sum(int(v.size) * v.dtype.itemsize
                       for k, v in cache.items())
        used = self.num_pages - self.pool.available()
        total = 0
        for k, v in cache.items():
            nbytes = int(v.size) * v.dtype.itemsize
            if k.endswith("_pages"):
                total += (nbytes // self.num_pages) * used
            else:                   # page_table + dense per-lane leaves
                total += nbytes
        return total + self.pool.refcount.nbytes

    def paged_stats(self) -> Dict[str, Any]:
        """Prefix-cache / paging counters for benchmarks and tests."""
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
            "kv_bytes_resident": self.kv_bytes_resident(),
            "free_pages": (self.pool.available() if self._paged else None),
            "prefix_entries": (self.pool.prefix_entries()
                               if self._paged else 0),
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
                self._prefill_len),
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
        if not self._paged:
            return
        expected = np.zeros(self.num_pages, np.int64)
        expected[GARBAGE_PAGE] = 1
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            for phys in self._pt_host[slot]:
                if int(phys) != GARBAGE_PAGE:
                    expected[int(phys)] += 1
        expected += self.pool.entry_page_refs()
        actual = np.asarray(self.pool.refcount, np.int64)
        if not np.array_equal(expected, actual):
            bad = np.nonzero(expected != actual)[0]
            raise AssertionError(
                f"refcount leak: pages {bad.tolist()} expected "
                f"{expected[bad].tolist()} got {actual[bad].tolist()}")


# -- metric-backed attributes (the single stats surface) ---------------------
# The ad-hoc counters of PRs 1-8 (prefill_s/decode_s timers, paged and
# lifecycle tallies) now LIVE in the MetricsRegistry; the attribute names
# every test/bench/engine already uses are preserved as read-write
# properties over the registry cells, so `sched.preemptions += 1`,
# `sched.prefill_s = 0.0` (bench warmup resets) and
# `metrics.snapshot()["sched.preemptions"]` all see one number.

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
