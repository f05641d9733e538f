import json

import pytest

import trace_reduce
from cells import BENCH

DATA = BENCH / "tests" / "data" / "trace_small.json"


def _events():
    raw = json.loads(DATA.read_text())
    return {"ops": {c: [tuple(e) for e in v] for c, v in raw["ops"].items()},
            "modules": {c: [tuple(e) for e in v]
                        for c, v in raw["modules"].items()},
            "host": [tuple(e) for e in raw["host"]]}, raw["expect"]


def test_busy_idle_and_kernel_time_of_a_small_trace():
    ev, want = _events()
    tr = trace_reduce.reduce(ev, window_s=want["window_s"])
    assert tr["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert 1 - tr["busy_s"] / tr["window_s"] == pytest.approx(
        want["idle_share"], rel=1e-9)
    n, s = trace_reduce.op_time(tr, "decode_attention_paged")
    assert (n, s) == (want["kernel_n"], pytest.approx(want["kernel_s"]))
    n, s = trace_reduce.module_time(tr, "jit__step")
    assert (n, s) == (want["step_n"], pytest.approx(want["step_s"]))
    gaps = tr["breakdown"]["idle_gaps"]
    assert gaps[0] == [want["longest_gap_label"],
                       pytest.approx(want["longest_gap_s"])]
    assert len(tr["breakdown"]["device_ops"]) <= 10


def test_union_merges_overlaps():
    ev = [("a", 0, 10), ("b", 5, 10), ("c", 30, 5), ("d", 31, 1)]
    assert trace_reduce.union(ev) == [(0, 15), (30, 35)]


def test_host_spans_from_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4)).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("tick"):
        f(jnp.ones(4)).block_until_ready()
    jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    ev = trace_reduce.load(path)
    assert [h[0] for h in ev["host"]] == ["tick"]
    assert ev["ops"] == {}          # no TPU plane on the CPU


def test_a_real_v5e_trace_extract():
    raw = json.loads((BENCH / "tests" / "data" / "trace_v5e.json").read_text())
    ev = {k: raw[k] for k in ("ops", "modules", "host")}
    ops = ev["ops"]["0"]
    span = (max(s + d for _, s, d in ops) - min(s for _, s, d in ops)) / 1e9
    tr = trace_reduce.reduce(ev, window_s=span)
    # busy time: the union of the op intervals, counted here on a 1 ns grid
    lo = int(min(s for _, s, _ in ops))
    covered = bytearray(int(span * 1e9) + 2)
    for _, s, d in ops:
        covered[int(s) - lo:int(s + d) - lo] = b"\x01" * (int(s + d) - int(s))
    assert tr["busy_s"] == pytest.approx(sum(covered) / 1e9, rel=1e-3)
    assert 0 < tr["busy_s"] <= span
    kernel = [d for n, _, d in ops if n.startswith("%decode_attention_paged")]
    assert trace_reduce.op_time(tr, "decode_attention_paged") == \
        (len(kernel), pytest.approx(sum(kernel) / 1e9))
    # a scan's %while spans its body: busy, but not an op of its own
    assert any(n.startswith("%while") for n, _, _ in ops)
    assert not any(n.startswith("%while") for n in tr["ops"])
    assert trace_reduce.module_time(tr, "jit__set_pt_entry")[0] == 1
    assert {g[0] for g in tr["breakdown"]["idle_gaps"]} <= {
        "tick", "retire-check", "submit", "none"}
