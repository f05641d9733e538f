"""Serving telemetry: metrics registry, Chrome-trace export, and the
request-lifecycle instrumentation threaded through the scheduler.

The load-bearing guarantees:

  * log-bucketed histogram quantiles track numpy percentiles within the
    bucket-growth error bound (~4.5% at the default growth),
  * the Chrome trace_event export is well-formed (spans nest, async
    begin/end pair per uid, events sorted by timestamp) and a full run
    renders every lifecycle transition — submit, admit, prefix hit/miss,
    first token, preempt, finish-with-reason — including preempted and
    EOS-finished requests driven by the fault injector,
  * telemetry adds ZERO device->host transfers per token: both the
    telemetry=None and the telemetry-enabled scheduler tick under a hard
    transfer guard, with identical sync counters,
  * the legacy counters (``prefill_s``, ``paged_stats()``,
    ``lifecycle_stats()``) and the registry are the SAME cells — one
    stats surface.
"""
import json
import math

import jax
import numpy as np
import pytest

from repro import models
from repro.configs.base import get_config, reduced
from repro.runtime.faults import AllocFault, ScriptedFaults
from repro.runtime.scheduler import ContinuousBatchingScheduler, Request
from repro.runtime.telemetry import (Counter, Gauge, Histogram,
                                     MetricsRegistry, Telemetry, Tracer)

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def tiny():
    cfg = reduced(get_config("tinyllama-1.1b"))
    params = models.init_params(cfg, KEY)
    return cfg, params


def _sched(cfg, params, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("cache_len", 64)
    kw.setdefault("max_new_cap", 16)
    return ContinuousBatchingScheduler(cfg, params, **kw)


# prompts long enough (plen 14) that decode crosses a page boundary
P0 = [3] + [5, 7] * 6 + [11]
P1 = [4] + [5, 7] * 6 + [11]


# ---------------------------------------------------------------------------
# histogram / registry primitives
# ---------------------------------------------------------------------------

def test_histogram_quantiles_track_numpy():
    """p50/p90/p99 of a lognormal latency-shaped sample agree with numpy
    percentiles within the documented relative error bound."""
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=-4.0, sigma=1.2, size=5000)  # ~ms scale
    h = Histogram()
    for v in samples:
        h.record(float(v))
    for q in (0.50, 0.90, 0.99):
        want = float(np.percentile(samples, q * 100))
        got = h.quantile(q)
        # bucket rep is off by <= sqrt(growth); allow 2 buckets of slack
        assert abs(got - want) / want < 0.10, (q, got, want)
    assert abs(h.mean - samples.mean()) / samples.mean() < 1e-9
    snap = h.snapshot()
    assert snap["count"] == 5000
    assert snap["min"] == samples.min() and snap["max"] == samples.max()


def test_histogram_edges_and_multiplicity():
    h = Histogram(lo=1e-3, hi=1e3)
    assert math.isnan(h.quantile(0.5))           # empty
    h.record(0.0)                                # underflow -> exact min
    h.record(1e9)                                # overflow  -> exact max
    h.record(0.5, n=98)                          # bulk multiplicity
    assert h.count == 100
    assert h.quantile(0.0) == 0.0
    assert h.quantile(1.0) == 1e9
    assert abs(h.quantile(0.5) - 0.5) / 0.5 < 0.05
    # quantiles never escape the observed [min, max] range
    assert 0.0 <= h.quantile(0.001) <= 1e9


def test_registry_get_or_create_reset_and_prefix():
    reg = MetricsRegistry()
    assert reg.counter("a") is reg.counter("a")
    reg.counter("sched.finish.eos").inc(3)
    reg.counter("sched.finish.length").inc()
    reg.gauge("g").set(7)
    reg.histogram("h").record(2.0)
    assert reg.counters_with_prefix("sched.finish.") == {"eos": 3,
                                                         "length": 1}
    snap = reg.snapshot()
    assert snap["sched.finish.eos"] == 3 and snap["g"] == 7
    assert snap["h"]["count"] == 1
    c = reg.counter("a")
    reg.reset()
    assert c is reg.counter("a") and c.value == 0   # identity preserved
    assert reg.histogram("h").count == 0


def test_counter_gauge_cells():
    c, g = Counter(), Gauge()
    c.inc()
    c.inc(2.5)
    g.set(4)
    g.set(1)
    assert c.value == 3.5 and g.value == 1


# ---------------------------------------------------------------------------
# tracer / Chrome export
# ---------------------------------------------------------------------------

def test_span_nesting_and_ordering(tmp_path):
    tr = Tracer()
    with tr.span("outer", args={"k": 1}):
        with tr.span("inner"):
            tr.instant("mark")
    tr.async_begin("life", 5, tid=5)
    tr.async_end("life", 5, tid=5)
    doc = tr.to_chrome_trace()
    evs = doc["traceEvents"]
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts)                      # export is time-ordered
    by = {e["name"]: e for e in evs}
    outer, inner, mark = by["outer"], by["inner"], by["mark"]
    # inner span (and the instant) nest strictly inside outer
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert outer["ts"] <= mark["ts"] <= outer["ts"] + outer["dur"]
    assert by["life"]["ph"] == "b" or any(
        e["name"] == "life" and e["ph"] == "b" for e in evs)
    assert any(e["name"] == "life" and e["ph"] == "e" for e in evs)
    path = tmp_path / "t.json"
    tr.export(str(path))
    loaded = json.loads(path.read_text())        # valid strict JSON
    assert loaded["traceEvents"]


def test_tracer_bounds_memory():
    tr = Tracer(max_events=3)
    for i in range(10):
        tr.instant(f"e{i}")
    assert len(tr.events) == 3 and tr.dropped == 7
    assert tr.to_chrome_trace()["otherData"]["dropped_events"] == 7


# ---------------------------------------------------------------------------
# full lifecycle trace through the scheduler
# ---------------------------------------------------------------------------

def _names(evs, uid=None, ph=None):
    out = []
    for e in evs:
        if uid is not None and e.get("tid") != uid:
            continue
        if ph is not None and e.get("ph") != ph:
            continue
        out.append(e["name"])
    return out


def test_lifecycle_trace_preempt_and_eos(tiny, tmp_path):
    """One exported trace containing a preempted request (injected
    first-touch exhaustion) and an EOS-finished request renders every
    lifecycle transition.  Two scheduler runs share one Telemetry —
    exactly how an engine rebuild composes."""
    cfg, params = tiny
    tel = Telemetry()

    # run A — preemption: fault the first mid-decode page touch
    faults = ScriptedFaults(
        alloc=[AllocFault(site="first_touch", after_tick=2)])
    s = _sched(cfg, params, kv_layout="paged", page_size=16, faults=faults,
               telemetry=tel)
    reqs = [Request(uid=0, prompt=list(P0), max_new_tokens=8),
            Request(uid=1, prompt=list(P1), max_new_tokens=8)]
    for r in reqs:
        s.submit(r)
    s.run()
    assert faults.fired and s.preemptions >= 1

    # run B — EOS: stop at a token the greedy stream provably emits
    probe = _sched(cfg, params)
    pr = Request(uid=9, prompt=[3, 5, 7], max_new_tokens=8)
    probe.submit(pr)
    probe.run()
    eos = pr.output[3]
    se = _sched(cfg, params, eos_id=eos, eos_check_interval=2,
                telemetry=tel)
    re = Request(uid=2, prompt=[3, 5, 7], max_new_tokens=8)
    se.submit(re)
    se.run()
    assert re.finish_reason == "eos"

    path = tmp_path / "trace.json"
    tel.export_chrome_trace(str(path))
    evs = json.loads(path.read_text())["traceEvents"]

    # every request: one async lifecycle begin/end pair on its own row
    for uid in (0, 1, 2):
        assert _names(evs, uid=uid, ph="b") == ["lifecycle"]
        assert _names(evs, uid=uid, ph="e") == ["lifecycle"]
        inst = _names(evs, uid=uid, ph="i")
        assert inst[0] == "submit" and "admit" in inst
        assert "first_token" in inst and "finish" in inst
    assert "prefix_miss" in _names(evs, uid=0, ph="i")  # paged run
    # the preempted request re-admits: preempt between its two admits
    pre_inst = None
    for uid in (0, 1):
        inst = _names(evs, uid=uid, ph="i")
        if "preempt" in inst:
            pre_inst = inst
            # requeue skips submit (front-of-queue) but re-admits
            assert inst.count("submit") == 1
            assert inst.count("admit") == 2
            assert inst.index("preempt") < inst.index("finish")
    assert pre_inst is not None, "no request recorded a preemption"
    # finish args carry the reason
    fins = [e for e in evs if e["name"] == "finish"]
    assert {f["args"]["finish_reason"] for f in fins} == {"eos", "length"}
    # scheduler row: tick spans with the nested phases + fault instants
    all_names = {e["name"] for e in evs}
    assert {"tick", "step_dispatch", "admit"} <= all_names
    assert "fault.alloc_fail" in all_names
    assert "eos_mask_fetch" in all_names
    # metrics side: finite quantiles with the right cardinalities
    snap = tel.metrics.snapshot()
    assert snap["req.ttft_s"]["count"] == 3      # once per request
    assert math.isfinite(snap["req.ttft_s"]["p99"])
    assert math.isfinite(snap["req.itl_s"]["p50"])
    assert snap["req.e2e_s"]["count"] == 3
    assert snap["sched.finish.eos"] == 1
    assert snap["sched.finish.length"] == 2


def test_itl_histogram_counts_inter_token_gaps(tiny):
    """A request producing n tokens records exactly n-1 inter-token
    gaps (anchored at the retirement fetch)."""
    cfg, params = tiny
    s = _sched(cfg, params)
    s.submit(Request(uid=0, prompt=[3, 5, 7], max_new_tokens=6))
    s.submit(Request(uid=1, prompt=[4, 5, 7], max_new_tokens=4))
    s.run()
    snap = s.metrics.snapshot()
    assert snap["req.itl_s"]["count"] == (6 - 1) + (4 - 1)
    assert snap["req.ttft_s"]["count"] == 2
    assert snap["req.queue_s"]["count"] == 2


def test_preempted_request_records_one_ttft(tiny):
    """Preempt-and-requeue must not double-count TTFT: the first
    dispatch is the first token."""
    cfg, params = tiny
    faults = ScriptedFaults(
        alloc=[AllocFault(site="first_touch", after_tick=2)])
    s = _sched(cfg, params, kv_layout="paged", page_size=16, faults=faults)
    reqs = [Request(uid=i, prompt=list(p), max_new_tokens=8)
            for i, p in enumerate([P0, P1])]
    for r in reqs:
        s.submit(r)
    s.run()
    assert s.preemptions >= 1
    assert s.metrics.snapshot()["req.ttft_s"]["count"] == 2


# ---------------------------------------------------------------------------
# zero-host-syncs guard: telemetry off AND on
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("enabled", [False, True])
def test_telemetry_adds_zero_host_syncs(tiny, enabled):
    """Ticks run under a hard device->host transfer guard with telemetry
    enabled — tracing must never read device data per token."""
    cfg, params = tiny
    tel = Telemetry() if enabled else None
    s = _sched(cfg, params, kv_layout="paged", page_size=16, telemetry=tel)
    for uid in range(2):
        s.submit(Request(uid=uid, prompt=[1 + uid, 2, 3],
                         max_new_tokens=12))
    s.tick()              # admission tick (prefill h2d allowed)
    with jax.transfer_guard_device_to_host("disallow"):
        for _ in range(8):
            s.tick()
    assert s.host_syncs == 0
    s.run()
    assert s.host_syncs == 2          # exactly one fetch per request
    if enabled:
        assert tel.metrics.snapshot()["req.itl_s"]["count"] == 22


# ---------------------------------------------------------------------------
# one stats surface: legacy counters are registry views
# ---------------------------------------------------------------------------

def test_legacy_counters_are_registry_cells(tiny):
    cfg, params = tiny
    s = _sched(cfg, params, kv_layout="paged", page_size=16)
    s.submit(Request(uid=0, prompt=list(P0), max_new_tokens=4))
    s.run()
    # attribute read == registry read
    assert s.tokens_generated == s.metrics.counter(
        "sched.tokens_generated").value == 4
    assert s.host_syncs == s.metrics.counter("sched.host_syncs").value == 1
    assert s.prefill_s == s.metrics.counter("sched.prefill_s").value > 0
    # attribute WRITE lands in the registry (bench reset idiom)
    s.tokens_generated = 0
    assert s.metrics.counter("sched.tokens_generated").value == 0
    # finish_reasons reconstructs from sched.finish.* counters
    assert s.finish_reasons == {"length": 1}
    assert s.lifecycle_stats()["finish_reasons"] == {"length": 1}
    # paged_stats reads the same cells
    ps = s.paged_stats()
    assert ps["admissions"] == s.admissions
    assert ps["lru_evictions"] == s.metrics.counter("pool.evictions").value


def test_registry_survives_engine_scheduler_rebuild(tiny):
    """ServingEngine rebuilds the scheduler when max_new_cap grows; a
    provided Telemetry keeps one registry across rebuilds."""
    from repro.serving.engine import ServingEngine
    cfg, params = tiny
    tel = Telemetry()
    eng = ServingEngine(cfg, params, max_batch=2, cache_len=64,
                        telemetry=tel)
    eng.generate_batch([Request(uid=0, prompt=[3, 5, 7],
                                max_new_tokens=4)])
    eng.generate_batch([Request(uid=1, prompt=[3, 5, 7],
                                max_new_tokens=32)])  # forces rebuild
    snap = tel.metrics.snapshot()
    assert snap["req.ttft_s"]["count"] == 2      # both runs, one registry
    assert snap["sched.tokens_generated"] == 36


# ---------------------------------------------------------------------------
# diagnostics on failure paths
# ---------------------------------------------------------------------------

def test_watchdog_error_carries_snapshot(tiny):
    cfg, params = tiny
    faults = ScriptedFaults(
        alloc=[AllocFault(site="admission", count=10**9)])
    s = _sched(cfg, params, kv_layout="paged", page_size=16,
               faults=faults, watchdog_ticks=10)
    s.submit(Request(uid=42, prompt=[3, 5, 7], max_new_tokens=4))
    with pytest.raises(RuntimeError, match="no progress") as ei:
        s.run()
    msg = str(ei.value)
    assert "free pages" in msg and "lane ages" in msg
    assert "last tick" in msg


def test_cancel_and_timeout_attach_diagnostics(tiny):
    cfg, params = tiny
    faults = ScriptedFaults(at_tick={3: lambda sch: sch.cancel(1)})
    s = _sched(cfg, params, faults=faults)
    reqs = [Request(uid=0, prompt=[3, 5, 7], max_new_tokens=8),
            Request(uid=1, prompt=[4, 5, 7], max_new_tokens=8),
            Request(uid=2, prompt=[5, 5, 7], max_new_tokens=8,
                    deadline_s=0.0)]     # expires before admission
    for r in reqs:
        s.submit(r)
    s.run()
    assert reqs[1].finish_reason == "cancelled"
    assert reqs[2].finish_reason == "timeout"
    for r in (reqs[1], reqs[2]):
        d = r.diagnostics
        assert d is not None
        assert {"tick", "free_pages", "free_lanes",
                "last_tick_ms"} <= set(d)
    assert reqs[0].diagnostics is None   # clean finishes carry none


# ---------------------------------------------------------------------------
# scheduler phases as profiler spans
# ---------------------------------------------------------------------------

# each phase's innermost enclosing phase (None: no enclosing phase)
SPAN_PARENTS = {
    "sched.submit": {None}, "sched.tick": {None},
    "sched.admit": {"sched.tick"},
    "sched.prefix_lookup": {"sched.admit"}, "sched.alloc": {"sched.admit"},
    "sched.prefill": {"sched.admit"},
    "sched.suffix_prefill": {"sched.admit"},
    "sched.prefix_register": {"sched.admit"},
    "sched.prepare_writes": {"sched.tick", "sched.suffix_prefill"},
    "sched.pt_update": {"sched.prepare_writes", "sched.admit",
                        "sched.suffix_prefill", "sched.retire"},
    "sched.step_dispatch": {"sched.tick"}, "sched.account": {"sched.tick"},
    "sched.eos_mask_fetch": {"sched.tick"}, "sched.retire": {"sched.tick"},
    "sched.retire_fetch": {"sched.retire"},
}


def _profiled_spans(tmp_path, body):
    """Run ``body`` under the JAX profiler; the ``sched.*`` events of the
    trace's host plane as ``(name, start_ns, end_ns, stats)``."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    pd = ProfileData.from_file(str(next(tmp_path.rglob("*.xplane.pb"))))
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for p in pd.planes if p.name == "/host:CPU"
            for line in p.lines for e in line.events
            if e.name.startswith("sched.")]


def _innermost_parent(ev, events):
    """The shortest other event that encloses ``ev`` (None if none)."""
    best = None
    for other in events:
        if other is ev or not (other[1] <= ev[1] and ev[2] <= other[2]):
            continue
        if other[2] - other[1] == ev[2] - ev[1] and other[0] == ev[0]:
            continue
        if best is None or other[2] - other[1] < best[2] - best[1]:
            best = other
    return None if best is None else best[0]


def test_scheduler_phases_reach_the_profiler_trace(tiny, tmp_path):
    """With ``jax.profiler`` running and no ``telemetry=``, a paged
    scheduler ticked through a cold admission, a prefix-hit admission, a
    page boundary, the EOS mask fetch and retirements writes every phase
    of ``telemetry.SPANS`` into the trace's host plane, nested as the
    scheduler nests them, with integer args as event stats."""
    from repro.runtime.telemetry import SPANS
    cfg, params = tiny
    # a stop token turns on the periodic done-mask fetch
    s = _sched(cfg, params, kv_layout="paged", page_size=16,
               eos_id=cfg.vocab_size - 1, eos_check_interval=1)

    def body():
        s.submit(Request(uid=0, prompt=list(P0), max_new_tokens=6))
        s.run()                       # cold admission, page boundary
        s.submit(Request(uid=1, prompt=list(P0), max_new_tokens=4))
        s.run()                       # prefix hit: suffix prefill

    events = _profiled_spans(tmp_path, body)
    names = {e[0] for e in events}
    assert names == set(SPANS)
    for ev in events:
        assert _innermost_parent(ev, events) in SPAN_PARENTS[ev[0]], ev[:3]
    admits = [e for e in events if e[0] == "sched.admit"]
    assert sorted(e[3]["uid"] for e in admits) == [0, 1]
    assert {e[3]["plen"] for e in admits} == {len(P0)}
    assert s.metrics.counter("sched.pt_updates").value == sum(
        e[0] == "sched.pt_update" for e in events)
    assert s.metrics.counter("pool.prefix_key_tokens").value > 0


def test_phase_spans_keep_their_chrome_names(tiny):
    """With ``telemetry=`` the same calls record Chrome spans under the
    phase's bare name, and an unknown phase is refused."""
    from repro.runtime.telemetry import SPANS, Span
    cfg, params = tiny
    tel = Telemetry()
    s = _sched(cfg, params, kv_layout="paged", page_size=16, telemetry=tel)
    s.submit(Request(uid=0, prompt=list(P0), max_new_tokens=4))
    s.run()
    xs = {e["name"] for e in tel.tracer.events if e["ph"] == "X"}
    assert xs <= {n[len("sched."):] for n in SPANS}
    assert {"submit", "tick", "admit", "prefill", "retire",
            "retire_fetch", "account"} <= xs
    with pytest.raises(ValueError, match="unknown scheduler span"):
        Span("tock")
