"""The harness end to end on the CPU, with the look for a chip skipped:
runs that serve correctly are judged correct, and a run whose served
tokens are altered where they are produced is judged not correct."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import pytest

import peaks
import run
from cells import BENCH

ROOT = BENCH.parent


@pytest.fixture
def cpu_peaks(monkeypatch):
    # the CPU has no published peaks; borrow the v5e row for the readers
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])


@pytest.mark.parametrize("arrival", ["backlog", "poisson"])
@pytest.mark.parametrize("trace", [False, True])
def test_run_is_correct(tiny_cell, cpu_peaks, arrival, trace):
    cell = tiny_cell(arrival)
    result, det = run.run_cell(cell, 2**31 + 3, 2.0, trace, log=lambda s: 0)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert det["tokens_compared"] >= cell.traffic["check_tokens"]
    names = {m["name"] for m in (cell.per_layer if trace
                                 else cell.end_to_end)}
    assert set(result["metrics"]) <= names
    if not trace:
        assert set(result["metrics"]) == names
        assert all(v["value"] > 0 for v in result["metrics"].values())
    assert list(result)[-1] == "checks"
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(result["device"])


def test_altered_tokens_are_not_correct(tiny_cell, monkeypatch):
    from repro.runtime import scheduler
    sample = scheduler._sample

    def altered(key, logits, temp):
        return (sample(key, logits, temp) + 1) % logits.shape[-1]

    monkeypatch.setattr(scheduler, "_sample", altered)
    result, det = run.run_cell(tiny_cell("backlog"), 11, 2.0, False,
                               log=lambda s: 0)
    gap = result["checks"]["widest_logit_gap"]
    assert not result["correct"]
    assert gap["value"] > gap["limit"]


def test_float8_control_is_not_correct(tiny_cell):
    # the control in the program's place, through the same checks
    cell = tiny_cell("backlog")
    limit = cell.config["check"]["widest_logit_gap"]
    for seed in (1, 2, 3):
        result, det = run.run_cell(cell, seed, 1.0, False, control=True,
                                   log=lambda s: 0)
        assert result["correct"] and det["widest_gap"] < limit / 3
        assert det["control_correct"] is False
        gap = det["control_checks"]["widest_logit_gap"]
        assert gap["limit"] == limit and gap["value"] > limit


def _bench(args, cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_exits_without_a_tpu():
    p = _bench(["--workload", "qwen3-8b-pp3.chat-backlog", "--seed", "1",
                "--seconds", "1", "--trace", "0"], ROOT)
    assert p.returncode == 2
    assert "{" not in p.stdout


def test_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench(["--workload", "qwen3-8b-pp3.chat-backlog", "--seed", "1",
                "--seconds", "1", "--trace", "0"], tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout
