"""Perf observability: Prometheus exposition, the live /metrics
endpoint, roofline closed forms, SLO/goodput math, and the accountant's
zero-host-syncs guarantee.

The load-bearing guarantees:

  * ``MetricsRegistry.to_prometheus`` emits legal text exposition 0.0.4
    — sanitized names, ``_total`` counters, summary quantile lines —
    and ``MetricsServer`` serves it live (plus ``/healthz``) from a
    daemon thread,
  * the roofline accountant's analytic KV-read bytes reproduce the
    quantization closed form exactly — bf16/int8 = ``2D/(D+4)`` — and
    the paged layout block-rounds to page granularity while agreeing
    with the ring layout at page-aligned context lengths,
  * SLO attainment follows the documented rules: per-request budgets
    override scheduler defaults, unbudgeted requests stay out of the
    goodput denominator, cancellations are excluded, violations of
    either leg count the request as missed,
  * per-tick roofline accounting runs under a hard device->host
    transfer guard — the accountant reads cache *metadata* and host
    mirrors only,
  * ``install_flush_on_exit`` makes an interrupted run still write a
    loadable Chrome trace, exactly once, and uninstalls cleanly.
"""
import json
import math
import signal
import urllib.error
import urllib.request

import jax
import pytest

from repro import models
from repro.configs.base import get_config, reduced
from repro.runtime.metrics_http import PROM_CONTENT_TYPE, MetricsServer
from repro.runtime.scheduler import ContinuousBatchingScheduler, Request
from repro.runtime.telemetry import MetricsRegistry, Telemetry, prom_name

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


# ---------------------------------------------------------------------------
# Prometheus text exposition
# ---------------------------------------------------------------------------

def test_prom_name_sanitization():
    assert prom_name("req.ttft_s") == "req_ttft_s"
    assert prom_name("sched.finish.eos") == "sched_finish_eos"
    assert prom_name("a-b/c d") == "a_b_c_d"
    assert prom_name("9lives") == "_9lives"
    assert prom_name("ok:colons_are_legal") == "ok:colons_are_legal"


def test_to_prometheus_exposition():
    reg = MetricsRegistry()
    reg.counter("sched.host_syncs").inc(3)
    reg.gauge("slo.goodput").set(0.5)
    h = reg.histogram("req.ttft_s")
    for v in (0.01, 0.02, 0.04):
        h.record(v)
    text = reg.to_prometheus()
    assert text.endswith("\n")
    # counters: sanitized name + conventional _total suffix
    assert "# TYPE sched_host_syncs_total counter" in text
    assert "sched_host_syncs_total 3.0" in text
    # gauges: as-is
    assert "# TYPE slo_goodput gauge" in text
    assert "slo_goodput 0.5" in text
    # histograms: summaries with quantile sample lines + _sum/_count
    assert "# TYPE req_ttft_s summary" in text
    for q in ("0.5", "0.9", "0.99"):
        assert f'req_ttft_s{{quantile="{q}"}}' in text
    assert "req_ttft_s_count 3" in text
    sum_line = [ln for ln in text.splitlines()
                if ln.startswith("req_ttft_s_sum ")][0]
    assert float(sum_line.split()[1]) == pytest.approx(0.07)


def test_to_prometheus_empty_histogram_is_nan():
    reg = MetricsRegistry()
    reg.histogram("empty.hist")
    text = reg.to_prometheus()
    assert 'empty_hist{quantile="0.5"} NaN' in text
    assert "empty_hist_count 0" in text


# ---------------------------------------------------------------------------
# live /metrics + /healthz endpoint
# ---------------------------------------------------------------------------

def test_metrics_server_serves_live_registry():
    reg = MetricsRegistry()
    reg.counter("a.b").inc(2)
    fail = {"on": False}

    def health_extra():
        if fail["on"]:
            raise RuntimeError("degraded")
        return {"lanes": 2}

    srv = MetricsServer(reg, port=0, health_extra=health_extra)
    port = srv.start()
    assert port > 0 and srv.url == f"http://127.0.0.1:{port}"
    try:
        with urllib.request.urlopen(f"{srv.url}/metrics") as r:
            assert r.status == 200
            assert r.headers["Content-Type"] == PROM_CONTENT_TYPE
            body = r.read().decode()
        assert "a_b_total 2.0" in body
        # scrapes render at request time: a later inc is visible
        reg.counter("a.b").inc()
        with urllib.request.urlopen(f"{srv.url}/metrics") as r:
            assert "a_b_total 3.0" in r.read().decode()
        with urllib.request.urlopen(f"{srv.url}/healthz") as r:
            doc = json.loads(r.read())
        assert doc["status"] == "ok" and doc["lanes"] == 2
        assert doc["uptime_s"] >= 0
        # a broken health_extra must not 500 the liveness probe
        fail["on"] = True
        with urllib.request.urlopen(f"{srv.url}/healthz") as r:
            assert "health_extra_error" in json.loads(r.read())
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{srv.url}/nope")
        assert ei.value.code == 404
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# roofline closed forms
# ---------------------------------------------------------------------------

def test_roofline_kv_read_matches_hand_formula(tiny):
    """bf16 ring: reading a ``v``-token prefix costs
    2(k+v) x layers x kv_heads x D x 2 bytes per token."""
    cfg, params = tiny
    s = _sched(cfg, params, kv_dtype="bf16")
    layers = int(s.state["cache"]["k"].shape[0])
    d = cfg.resolved_head_dim
    kvh = max(1, cfg.num_kv_heads)
    per_slot = 2 * layers * kvh * d * 2
    for v in (1, 17, 64):
        assert s.roofline.kv_read_bytes(v) == per_slot * v


def test_roofline_bf16_over_int8_is_2d_over_d_plus_4(tiny):
    """The quantization win the accountant reports is the exact closed
    form: int8 pays D bytes + one 4-byte f32 scale where bf16 pays 2D."""
    cfg, params = tiny
    rb = _sched(cfg, params, kv_dtype="bf16").roofline
    ri = _sched(cfg, params, kv_dtype="int8").roofline
    d = cfg.resolved_head_dim
    for v in (8, 48):
        # integer cross-multiplication: ratio == 2D/(D+4) EXACTLY
        assert rb.kv_read_bytes(v) * (d + 4) == ri.kv_read_bytes(v) * 2 * d


def test_roofline_paged_block_rounds_to_pages(tiny):
    cfg, params = tiny
    ring = _sched(cfg, params, kv_dtype="bf16").roofline
    paged = _sched(cfg, params, kv_dtype="bf16", kv_layout="paged",
                   page_size=16).roofline
    # mid-page contexts round up to the next page boundary...
    assert paged.kv_read_bytes(17) == paged.kv_read_bytes(32)
    assert paged.kv_read_bytes(17) > paged.kv_read_bytes(16)
    # ...and at page-aligned lengths paged agrees with the ring layout
    for v in (16, 32, 64):
        assert paged.kv_read_bytes(v) == ring.kv_read_bytes(v)
    # the pool is capacity-capped at pages_per_lane x page_size
    cap = paged.kv_read_bytes(64)
    assert paged.kv_read_bytes(10_000) == cap


def test_roofline_step_cost_and_ceiling(tiny):
    cfg, params = tiny
    rf = _sched(cfg, params, kv_dtype="bf16").roofline
    by1, fl1 = rf.step_cost([8])
    by2, fl2 = rf.step_cost([8, 8])
    # weights stream ONCE per batched step: two lanes cost less than 2x
    assert by1 < by2 < 2 * by1
    assert fl2 == pytest.approx(2 * fl1 - rf.step_cost([])[1], rel=1e-9) \
        or fl2 > fl1
    bpt = by1 / 1
    assert rf.roofline_tok_per_s(bpt) == pytest.approx(rf.hw.hbm_bw / bpt)
    mbu, mfu = rf.utilization(by1, fl1, elapsed_s=1.0)
    assert 0 < mbu < 1 and 0 < mfu < 1
    assert rf.utilization(by1, fl1, 0.0) == (0.0, 0.0)


# ---------------------------------------------------------------------------
# SLO attainment / goodput math
# ---------------------------------------------------------------------------

def test_goodput_requests_straddling_budgets(tiny):
    """One met, one TTFT miss, one ITL miss, one unbudgeted (out of the
    denominator), one cancelled (excluded) -> goodput = 1/3."""
    cfg, params = tiny
    s = _sched(cfg, params)
    s.submit(Request(uid=0, prompt=[1, 2, 3], max_new_tokens=4,
                     slo_ttft_s=1e6, slo_itl_s=1e6))          # met
    s.submit(Request(uid=1, prompt=[1, 2, 4], max_new_tokens=4,
                     slo_ttft_s=0.0))                         # ttft miss
    s.submit(Request(uid=2, prompt=[1, 2, 5], max_new_tokens=4,
                     slo_itl_s=0.0))                          # itl miss
    s.submit(Request(uid=3, prompt=[1, 2, 6], max_new_tokens=4))
    s.submit(Request(uid=4, prompt=[1, 2, 7], max_new_tokens=4,
                     slo_ttft_s=1e6))
    s.cancel(4)
    s.run()
    st = s.slo_stats()
    assert st["requests"] == 3
    assert st["met"] == 1
    assert st["ttft_violations"] == 1
    assert st["itl_violations"] == 1
    assert st["goodput"] == pytest.approx(1 / 3)
    assert s.metrics.gauge("slo.goodput").value == pytest.approx(1 / 3)


def test_goodput_scheduler_defaults_and_override(tiny):
    cfg, params = tiny
    s = _sched(cfg, params, slo_ttft_s=1e6, slo_itl_s=1e6)
    s.submit(Request(uid=0, prompt=[1, 2, 3], max_new_tokens=3))
    s.submit(Request(uid=1, prompt=[1, 2, 4], max_new_tokens=3,
                     slo_ttft_s=0.0))      # per-request override -> miss
    s.run()
    st = s.slo_stats()
    assert (st["requests"], st["met"]) == (2, 1)
    assert st["goodput"] == pytest.approx(0.5)


def test_goodput_none_until_budgeted_requests_finish(tiny):
    cfg, params = tiny
    s = _sched(cfg, params)                # no defaults, no budgets
    s.submit(Request(uid=0, prompt=[1, 2, 3], max_new_tokens=3))
    s.run()
    assert s.slo_stats()["goodput"] is None


# ---------------------------------------------------------------------------
# accounting is free of device->host syncs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layout_kw", [{}, {"kv_layout": "paged",
                                            "page_size": 16}])
def test_roofline_accounting_zero_host_syncs(tiny, layout_kw):
    """Per-tick accounting uses cache METADATA and host mirrors only —
    ticks advance the roofline counters under a hard transfer guard."""
    cfg, params = tiny
    s = _sched(cfg, params, kv_dtype="bf16", **layout_kw)
    for uid in range(2):
        s.submit(Request(uid=uid, prompt=[1 + uid, 2, 3],
                         max_new_tokens=12))
    s.tick()                  # admission tick (prefill h2d allowed)
    tok0 = s.metrics.counter("roofline.tokens").value
    by0 = s.metrics.counter("roofline.analytic_bytes").value
    with jax.transfer_guard_device_to_host("disallow"):
        for _ in range(8):
            s.tick()
    assert s.host_syncs == 0
    assert s.metrics.counter("roofline.tokens").value == tok0 + 16
    assert s.metrics.counter("roofline.analytic_bytes").value > by0
    s.run()
    rf = s.roofline_stats()
    # decode-path tokens only: the first token per lane comes from
    # prefill, so 2 lanes x (12 - 1) decode steps land in the account
    assert rf["tokens_accounted"] == 22
    assert rf["bytes_per_token"] > 0 and rf["flops_per_token"] > 0
    assert rf["roofline_tok_per_s"] > 0
    assert rf["mbu"] >= 0 and math.isfinite(rf["mbu"])
    # retirement recorded at least one achieved-vs-roofline window
    assert s.metrics.histogram("roofline.mbu").count >= 1


def test_telemetry_snapshot_gauges(tiny):
    cfg, params = tiny
    s = _sched(cfg, params, kv_layout="paged", page_size=16)
    s.submit(Request(uid=0, prompt=[1] * 14, max_new_tokens=4))
    s.submit(Request(uid=1, prompt=[1] * 14, max_new_tokens=4))
    s.tick()
    snap = s.telemetry_snapshot()
    assert 0.0 < snap["pool_occupancy_frac"] <= 1.0
    assert snap["prefix_hit_ratio"] is not None
    s.run()
    # tick-end gauges mirror the same cells into the registry
    reg = s.metrics.snapshot()
    assert "pool.occupancy_frac" in reg
    assert "sched.prefix_hit_ratio" in reg


# ---------------------------------------------------------------------------
# partial-trace flush on interrupt
# ---------------------------------------------------------------------------

def test_flush_on_interrupt_writes_loadable_trace(tmp_path):
    tel = Telemetry()
    tel.tracer.instant("partial-progress")
    path = tmp_path / "trace.json"
    prev = signal.getsignal(signal.SIGINT)
    uninstall = tel.install_flush_on_exit(str(path))
    try:
        with pytest.raises(KeyboardInterrupt):
            signal.raise_signal(signal.SIGINT)
        doc = json.loads(path.read_text())
        names = [e.get("name") for e in doc["traceEvents"]]
        assert "partial-progress" in names
        n_first = len(doc["traceEvents"])
        # flush is idempotent per install: a second interrupt still
        # raises but does not rewrite the file
        tel.tracer.instant("after-flush")
        with pytest.raises(KeyboardInterrupt):
            signal.raise_signal(signal.SIGINT)
        assert len(json.loads(path.read_text())["traceEvents"]) == n_first
    finally:
        uninstall()
    assert signal.getsignal(signal.SIGINT) is prev


class _FakeDevice:
    def __init__(self, kind):
        self.device_kind = kind


def test_hwspec_keys_peaks_by_device_kind(monkeypatch):
    """The v5e row carries its published peaks; the CPU row is the
    labelled test figure."""
    from repro.runtime.roofline import HWSpec
    monkeypatch.setattr(jax, "devices", lambda: [_FakeDevice("TPU v5 lite")])
    hw = HWSpec.detect()
    assert (hw.name, hw.peak_flops, hw.hbm_bw) == ("tpu-v5e", 197e12, 819e9)
    monkeypatch.setattr(jax, "devices", lambda: [_FakeDevice("cpu")])
    assert "test figure" in HWSpec.detect().name


@pytest.mark.parametrize("env", [{}, {"REPRO_HW_PEAK_FLOPS": "1e12",
                                      "REPRO_HW_HBM_BW": "1e11"}])
def test_hwspec_unknown_device_raises(monkeypatch, env):
    """An accelerator outside the table never inherits another's peaks,
    and the overrides cannot stand in for a missing row."""
    from repro.runtime.roofline import HWSpec
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    monkeypatch.setattr(jax, "devices", lambda: [_FakeDevice("TPU v4")])
    with pytest.raises(KeyError, match="TPU v4"):
        HWSpec.detect()


def test_no_interpret_mode_or_ref_fallback_on_tpu(monkeypatch):
    """On a TPU the kernels compile (interpret=True is refused) and auto
    decode resolves to pallas; an unknown backend is an error."""
    from repro.core.ops import resolve_decode_backend
    from repro.kernels import ops as kops
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kops._interpret(None) is False
    with pytest.raises(ValueError, match="interpret"):
        kops._interpret(True)
    assert resolve_decode_backend(None, quantized=True) == "pallas_q8"
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError):
        kops._interpret(None)
    with pytest.raises(RuntimeError):
        resolve_decode_backend("auto")
