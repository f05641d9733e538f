import json

import pytest

import cells
import spans
import trace_reduce
from cells import BENCH

DATA = BENCH / "tests" / "data" / "spans_small.json"
READERS = ("idle_admit_share.backlog", "idle_tick_share.backlog",
           "idle_untraced_share.backlog")


def _events():
    raw = json.loads(DATA.read_text())
    ev = {"ops": {c: [tuple(o) for o in v] for c, v in raw["ops"].items()},
          "spans": [tuple(s) for s in raw["spans"]]}
    return ev, raw["expect"]


def test_each_idle_instant_goes_to_the_innermost_open_span():
    ev, want = _events()
    iv = trace_reduce.union(ev["ops"]["0"])
    gaps = [(e0, s1) for (_, e0), (s1, _) in zip(iv, iv[1:])]
    groups, by_span = spans.attribute_gaps(gaps, ev["spans"])
    assert groups == pytest.approx(want["idle_ns"])
    assert by_span == pytest.approx(want["by_span_ns"])


def test_the_three_shares_sum_to_the_inter_op_idle():
    ev, want = _events()
    got = spans.attribute(ev, want["window_s"])
    shares = {}
    for name in READERS:
        ctx = type("Ctx", (), {"_span_attribution": got})()
        shares[name] = cells.reader(name)(ctx)
    iv = trace_reduce.union(ev["ops"]["0"])
    inter_op = sum(s1 - e0 for (_, e0), (s1, _) in zip(iv, iv[1:])) / 1e9
    assert sum(shares.values()) == pytest.approx(
        100 * inter_op / want["window_s"])
    idle_share = 100 * (1 - want["busy_s"] / want["window_s"])
    assert sum(shares.values()) < idle_share   # edges are in no gap
    assert shares["idle_admit_share.backlog"] == pytest.approx(
        100 * want["idle_ns"]["admit"] / 1e9 / want["window_s"])


def test_admit_host_time_leaves_out_the_prefill_dispatches():
    ev, want = _events()
    assert spans.admit_host_ns(ev["spans"]) == pytest.approx(
        want["admit_host_ns"])
    ctx = type("Ctx", (), {"_span_attribution":
                           spans.attribute(ev, want["window_s"])})()
    mean_ms = sum(want["admit_host_ns"]) / 2 / 1e6
    assert cells.reader("admit_host_ms.backlog")(ctx) == pytest.approx(
        mean_ms)


def test_no_attribution_without_scheduler_spans_or_device_ops():
    ev, want = _events()
    assert spans.attribute({**ev, "spans": []}, want["window_s"]) is None
    assert spans.attribute({**ev, "ops": {}}, want["window_s"]) is None
    ctx = type("Ctx", (), {"_span_attribution": None})()
    for name in READERS + ("admit_host_ms.backlog",):
        assert cells.reader(name)(ctx) is None


def test_scheduler_spans_from_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4)).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("tick"):
        with jax.profiler.TraceAnnotation("sched.tick"):
            with jax.profiler.TraceAnnotation("sched.admit", uid=3):
                f(jnp.ones(4)).block_until_ready()
    jax.profiler.stop_trace()
    ev = spans.load(next(tmp_path.rglob("*.xplane.pb")))
    assert [s[0] for s in ev["spans"]] == ["sched.tick", "sched.admit"]
    assert ev["ops"] == {}          # no TPU plane on the CPU
    assert spans.attribute(ev, 1.0) is None


def test_a_traced_run_reads_its_own_trace(tiny_cell, monkeypatch):
    """Through ``run.run_cell``: the readers find the run's trace file and
    the program's scheduler spans in it (the CPU has no device plane, so
    nothing is reported)."""
    import peaks
    import run
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    seen = []
    real = spans.attribute
    monkeypatch.setattr(spans, "attribute",
                        lambda ev, w: seen.append(ev) or real(ev, w))
    result, _ = run.run_cell(tiny_cell("backlog"), 5, 2.0, True,
                             log=lambda s: 0)
    assert len(seen) == 1
    assert {"sched.tick", "sched.admit", "sched.step_dispatch",
            "sched.retire"} <= {s[0] for s in seen[0]["spans"]}
    assert not {m for m in result["metrics"] if m.startswith(
        ("idle_admit", "idle_tick", "idle_untraced", "admit_host"))}
