"""A configuration, a traffic mix or a per-layer metric is added as
files and entries alone: the harness finds each by its name."""
import json
import shutil
from types import SimpleNamespace

import cells
from cells import BENCH


def test_new_cell_and_metric_from_files_only(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    config = json.loads((BENCH / "configs" / "qwen3-0.6b.json").read_text())
    (tmp_path / "bench" / "configs" / "qwen3-0.6b-short.json").write_text(
        json.dumps({**config, "engine": {**config["engine"],
                                         "cache_len": 1024}}))
    (tmp_path / "bench" / "traffic" / "short-backlog.json").write_text(
        json.dumps({"arrival": "backlog", "queue_per_lane": 2, "deck": 4,
                    "prompt_len": {"values": [64], "weights": [1]},
                    "output_len": {"dist": "uniform", "min": 8, "max": 16},
                    "temperature": 0.0, "check_tokens": 32}))
    (tmp_path / "bench" / "metrics" / "dummy_ms.short.py").write_text(
        "def read(ctx):\n"
        "    n, s = ctx.module_time(ctx.STEP)\n"
        "    return 1e3 * s / n if n else None\n")
    bench["configs"].append({**bench["configs"][1], "name": "qwen3-0.6b-short",
                             "file": "bench/configs/qwen3-0.6b-short.json"})
    bench["workloads"].append({"name": "qwen3-0.6b-short.short-backlog",
                               "config": "qwen3-0.6b-short",
                               "traffic": "short-backlog", "chips": 1,
                               "why": "a cell added by files alone"})
    for m in bench["end_to_end"]:
        if m["name"] == "out_tok_s":
            m["workloads"].append("qwen3-0.6b-short.short-backlog")
    bench["per_layer"].append({
        "name": "dummy_ms.short", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "model step", "moves": "out_tok_s",
        "workloads": ["qwen3-0.6b-short.short-backlog"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = cells.load("qwen3-0.6b-short.short-backlog", root=tmp_path)
    assert cell.engine["cache_len"] == 1024
    assert cell.traffic["prompt_len"]["values"] == [64]
    assert [m["name"] for m in cell.per_layer] == ["dummy_ms.short"]
    assert {m["name"] for m in cell.end_to_end} == {"out_tok_s", "setup_s"}
    read = cells.reader("dummy_ms.short", root=tmp_path)
    ctx = SimpleNamespace(STEP="jit__step",
                          module_time=lambda part: (4, 0.02))
    assert read(ctx) == 5.0
    # the cells already there are untouched
    old = cells.load("qwen3-0.6b.longdoc-backlog", root=tmp_path)
    assert "dummy_ms.short" not in [m["name"] for m in old.per_layer]
