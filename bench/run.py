"""Chip benchmark of the serving path: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration file
(``bench/configs/``) and a traffic mix (``bench/traffic/``).  A run makes
the weights on the device from the seed, builds the program's
``ServingEngine`` on them, warms up the cell's shapes (each prompt length
of the mix and the decode step), then drives the engine's scheduler
through ``submit()``/``tick()`` for ``--seconds`` (``bench/window.py``).
Afterwards it compares what the window served with the plain reference
(``bench/check.py``) and prints one JSON line last: with ``--trace 0`` the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics, read
from a profiler trace of 10 s of the cell's traffic (in the middle of a
backlog window, after a Poisson window: ``bench/window.py``) and from the
host's stamps of the window's requests.

It refuses to run without a TPU, or with fewer chips than the cell asks
for (exit code 2, no result line).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

import cells  # noqa: E402
import check  # noqa: E402
import peaks  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402
from window import Window, now  # noqa: E402

TRACE_S = 10.0     # length of the traced slice
# names the program's compiled programs and kernel carry in the trace
STEP, ADMIT = "jit__step", "jit__admit"
DECODE_ATTN = "decode_attention_paged"


def require_chips(n: int) -> dict:
    """The device as JAX reports it; exit 2 when JAX finds no TPU or fewer
    than ``n`` chips."""
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu" or info["count"] < n:
        print(f"bench: need {n} TPU chip(s), JAX found {info['count']} "
              f"{info['platform']} device(s); no result", file=sys.stderr)
        raise SystemExit(2)
    return info


def enable_cache() -> str:
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    # cache every program, small ones too, so that only a cell's first run
    # in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _pct(xs, q):
    return float(np.percentile(np.asarray(xs, float), q))


# -- end-to-end metrics: name -> f(window result, stamps) ----------------------

def _latencies(res, stamps):
    """Due time -> output on the host; a request still unfinished when the
    run gave up counts with the time it had waited by then."""
    return [stamps[u].get("done", res["end"]) - stamps[u]["due"]
            for u in res["in_window"]]


E2E = {
    "out_tok_s": lambda res, st: res["tokens"] / (res["t1"] - res["t0"]),
    "latency_p50_s": lambda res, st: _pct(_latencies(res, st), 50),
    "latency_p95_s": lambda res, st: _pct(_latencies(res, st), 95),
}


def warm_up(sched, mix, vocab):
    """Compile (or load from the cache) every program the window uses: an
    admission per prompt length of the mix, the decode step, the page
    table updates and the retirement fetch."""
    from repro.serving.engine import Request
    rng = np.random.default_rng(0)
    for i, plen in enumerate(mix["prompt_len"]["values"]):
        sched.submit(Request(uid=-1 - i,
                             prompt=rng.integers(0, vocab, plen).tolist(),
                             max_new_tokens=min(sched.page_size + 1,
                                                sched.max_new_cap)))
    sched.run()
    jax.block_until_ready(sched.state)


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             t_start: float = T_START, control: bool = False, log=print):
    """One run of ``cell``.  Returns ``(result, details)``: the result line
    and what the run saw besides (set-up split, readings, gaps)."""
    from repro.serving.engine import ServingEngine
    cfg, mix = cell.config, cell.traffic
    ref, fam = cells.reference(cfg), cells.family(cfg)
    split = {}
    t = now()
    params = jax.block_until_ready(ref.make_params(cfg, seed))
    split["weights_s"] = now() - t
    t = now()
    engine = ServingEngine(fam.arch_config(cfg), params, seed=seed,
                           **cell.engine)
    sched = engine.scheduler(max_new_cap=mix["output_len"]["max"])
    warm_up(sched, mix, cfg["vocab_size"])
    split["build_and_warm_s"] = now() - t
    trace_dir = pathlib.Path(tempfile.mkdtemp(prefix="bench_trace_")) \
        if trace else None
    win = Window(sched, mix, traffic.requests(mix, cfg["vocab_size"], seed),
                 trace_dir=trace_dir, trace_s=TRACE_S)
    t = now()
    if mix["arrival"] == "backlog":
        res = win.run_backlog(seconds)
    else:
        res = win.run_poisson(seconds)
    win.compiles.close()
    split["lead_in_s"] = res["t0"] - t
    setup_s = res["t0"] - t_start
    stamps = win.stamps
    device = jax.devices()[0]
    info = {"platform": device.platform, "kind": device.device_kind,
            "count": len(jax.devices()),
            # the CPU (tests only) reports no memory statistics
            "memory_peak_bytes": int((device.memory_stats() or {})
                                     .get("peak_bytes_in_use", 0))}
    lateness = [stamps[u]["submit"] - stamps[u]["due"]
                for u in res["in_window"]]
    log(f"set-up {setup_s:.3f}s: " + ", ".join(
        f"{k} {v:.3f}" for k, v in split.items()))
    log(f"window {res['t1'] - res['t0']:.3f}s: {len(res['in_window'])} "
        f"requests {'due' if mix['arrival'] == 'poisson' else 'retired'}, "
        f"{len(res['in_window']) - len(res['unfinished'])} done, "
        f"{len(res['unfinished'])} unfinished at the drain limit; "
        f"compiles inside the window {win.compiles.count} "
        f"({win.compiles.seconds:.3f}s)")
    if mix["arrival"] == "poisson" and lateness:
        log(f"generator lateness p50 {_pct(lateness, 50):.6f}s max "
            f"{max(lateness):.6f}s")
    else:
        log(f"queue depth after admission: min {win.min_queue} "
            f"(kept at {mix.get('queue_per_lane', 0) * sched.max_slots})")
    log(f"device {info['kind']} x{info['count']}, memory peak "
        f"{info['memory_peak_bytes']} bytes")

    metrics = {}
    breakdown = None
    if trace:
        ctx = _context(cell, fam, win, res, info)
        info["busy_s"] = ctx.trace["busy_s"]
        info["window_s"] = ctx.trace["window_s"]
        breakdown = ctx.trace["breakdown"]
        for m in cell.per_layer:
            v = cells.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        for m in cell.end_to_end:
            v = setup_s if m["name"] == "setup_s" else \
                E2E[m["name"]](res, stamps)
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    served = {u: (win.requests[u].prompt, win.requests[u].output)
              for u in res["in_window"] if win.requests[u].done}
    wanted = {u: win.requests[u].max_new_tokens for u in served}
    # free the program's state before the reference runs: the peak above
    # is the program's alone
    del win, sched
    engine._sched = None
    del engine
    gc.collect()
    checks, details = check.run(ref, cfg, params, served, wanted,
                                unfinished=len(res["unfinished"]), seed=seed,
                                min_tokens=mix["check_tokens"],
                                control=control, log=log)
    failed = checks["wrong_length"]["value"] + checks["unfinished"]["value"]
    result = {"correct": check.passed(checks),
              "attempted": len(res["in_window"]), "failed": int(failed),
              "metrics": metrics, "device": info}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    details.update(split=split, setup_s=setup_s,
                   window_s=res["t1"] - res["t0"])
    if control:
        details["control_correct"] = check.passed(details["control_checks"])
    return result, details


def _context(cell, fam, win, res, info):
    """What a per-layer reader may read: the reduced trace of the slice,
    the work the host dispatched in it, the configuration and the peaks."""
    sl = win.slice
    paths = sorted(pathlib.Path(win.trace_dir).rglob("*.xplane.pb"))
    tr = trace_reduce.reduce(trace_reduce.load(paths[-1]),
                             window_s=sl["t1"] - sl["t0"])
    kv_itemsize = {"bf16": 2, "int8": 1}[cell.engine["kv_dtype"]]
    return SimpleNamespace(
        STEP=STEP, ADMIT=ADMIT, DECODE_ATTN=DECODE_ATTN,
        module_time=lambda part: trace_reduce.module_time(tr, part),
        op_time=lambda part: trace_reduce.op_time(tr, part),
        cell=cell, config=cell.config, engine=cell.engine, trace=tr,
        steps=sl["steps"], admissions=sl["admissions"],
        stamps=win.stamps, window=res, peaks=peaks.peaks(info["kind"]),
        flops=fam, kv_itemsize=kv_itemsize)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.load(args.workload)
    require_chips(cell.chips)
    enable_cache()
    result, _ = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                         log=lambda s: print(s, flush=True))
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
