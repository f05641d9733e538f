"""Benchmark harness entry point: one benchmark per paper table/claim.

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run nin store  # substring filter
    PYTHONPATH=src python -m benchmarks.run --smoke    # CI subset

``--smoke`` runs a fast, CI-sized subset (and exports
``REPRO_BENCH_SMOKE=1`` so benchmarks can shrink their problem sizes) —
this is what .github/workflows/ci.yml executes so the perf scripts can't
silently rot.
"""
from __future__ import annotations

import os
import sys
import time
import traceback

from benchmarks import (bench_compression, bench_energy, bench_fftconv,
                        bench_kernels, bench_model_store, bench_nin_latency,
                        bench_roofline, bench_serving)
from repro.launch.compile_cache import enable_compile_cache

BENCHES = [
    ("nin_latency", bench_nin_latency.main),        # paper sec 1.1 (C6)
    ("model_store", bench_model_store.main),        # paper sec 2 (C4)
    ("compression", bench_compression.main),        # sec 2 + roadmap 7/8
    ("fftconv", bench_fftconv.main),                # roadmap 1
    ("kernels", bench_kernels.main),                # sec 1 operator set
    ("serving", bench_serving.main),                # sec 1.1 Nielsen budget
    ("energy", bench_energy.main),                  # sec 2 figs 10-12
    ("roofline", bench_roofline.main),              # deliverable (g)
]

SMOKE = ("model_store", "compression", "fftconv", "serving")


def main() -> None:
    enable_compile_cache()
    smoke = "--smoke" in sys.argv
    if smoke:
        os.environ["REPRO_BENCH_SMOKE"] = "1"
    filters = [a for a in sys.argv[1:] if not a.startswith("-")]
    failures = []
    t_all = time.perf_counter()
    for name, fn in BENCHES:
        if smoke and name not in SMOKE:
            continue
        if filters and not any(f in name for f in filters):
            continue
        t0 = time.perf_counter()
        try:
            fn()
            print(f"[{name}] done in {time.perf_counter()-t0:.1f}s\n")
        except Exception as e:  # noqa: BLE001 — report all, fail at end
            failures.append((name, repr(e)))
            traceback.print_exc()
    print(f"benchmarks total: {time.perf_counter()-t_all:.1f}s")
    if failures:
        for n, e in failures:
            print(f"FAILED {n}: {e}")
        raise SystemExit(1)


if __name__ == "__main__":
    main()
