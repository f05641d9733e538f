"""The harness's tests run on the CPU at tiny sizes:
``JAX_PLATFORMS=cpu python -m pytest bench/tests``."""
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import json  # noqa: E402

import pytest  # noqa: E402

# A Qwen3-shaped model small enough for the CPU, and a short mix; the
# widest-gap limit lies between its bfloat16 readings (0 to 0.0033 on
# seeds 1-8) and the float8 control's on the same seeds (0.0126 to 0.128)
TINY = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 512,
        "engine": {"max_batch": 4, "cache_len": 96, "kv_layout": "paged",
                   "kv_dtype": "bf16", "page_size": 16},
        "check": {"widest_logit_gap": 0.008}}
TINY_MIX = {"queue_per_lane": 2, "deck": 8, "rate_rps": 20, "lead_s": 1,
            "drain_s": 10, "prompt_len": {"values": [16, 32],
                                          "weights": [1, 1]},
            "output_len": {"dist": "loguniform", "min": 4, "max": 16},
            "temperature": 0.0, "check_tokens": 40}


@pytest.fixture
def tiny_cell():
    """``tiny_cell(arrival)``: a cell of the tiny model and mix that
    reports the metrics of the benchmark's cells with that arrival."""
    import cells

    def make(arrival="backlog"):
        config = json.loads((BENCH / "configs" / "qwen3-0.6b.json")
                            .read_text())
        config.update(TINY)
        bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
        suffix = "." + arrival
        e2e = [{"name": "setup_s", "unit": "s"}] + (
            [{"name": "out_tok_s", "unit": "tokens/s"}]
            if arrival == "backlog" else
            [{"name": "latency_p50_s", "unit": "s"},
             {"name": "latency_p95_s", "unit": "s"}])
        per_layer = [m for m in bench["per_layer"]
                     if m["name"].endswith(suffix)]
        return cells.Cell("tiny." + arrival, 1, config,
                          {**TINY_MIX, "arrival": arrival}, e2e, per_layer)
    return make
