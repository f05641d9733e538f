"""The Qwen3-MoE configuration at one chip's expert share: its family
module, reference weights, cells and per-layer readers, on the CPU."""
import json
from types import SimpleNamespace

import jax
import pytest

import cells
import peaks
import run
from cells import BENCH

CELL = "qwen3-235b-a22b-ep16.chat-backlog"
# the cell's keys at a tiny size in float32: 4 of 16 experts held
# (experts 4-7), top 4; the widest-gap limit lies between the program's
# readings (0 to 0.0029 on seeds 1-8) and the float8 control's on the
# same seeds (0.124 to 0.562)
TINY = {"hidden_size": 64, "moe_intermediate_size": 32,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
        "num_experts": 4, "num_experts_per_tok": 4,
        "torch_dtype": "float32",
        "published": {"num_hidden_layers": 94, "num_experts": 16,
                      "vocab_size": 151936},
        "engine": {"max_batch": 4, "cache_len": 96, "kv_layout": "paged",
                   "kv_dtype": "bf16", "page_size": 16},
        "check": {"widest_logit_gap": 0.03}}


def _config():
    return json.loads((BENCH / "configs" / "qwen3-235b-a22b-ep16.json")
                      .read_text())


def _tiny():
    m = {**_config(), **TINY}
    m["deployment"] = {**m["deployment"], "held_experts": [4, 4]}
    return m


fam = cells.family(_config())


def test_cells_load():
    moe = cells.load(CELL)
    assert moe.engine["max_batch"] == 64 and moe.chips == 1
    assert {m["name"] for m in moe.end_to_end} == {"out_tok_s", "setup_s"}
    names = {m["name"] for m in moe.per_layer}
    assert {"moe_roofline.backlog", "moe_share.backlog",
            "moe_rows_per_expert.backlog", "decode_step_ms.backlog",
            "mfu.backlog"} <= names


def test_published_shares():
    # hf:Qwen/Qwen3-235B-A22B at 8 of 128 experts: 222.8 M a layer
    # (attention 71.3 M, held experts 151.0 M, router 0.52 M), 5.66 GB in
    # bf16 with a vocabulary slice of 18,992 rows in and out
    m = _config()
    assert fam.layer_params(m) == 222_830_848
    assert fam.expert_params(m) == 150_994_944
    assert 2 * fam.model_params(m) == pytest.approx(5.66e9, rel=1e-3)
    assert fam.kv_bytes_per_token(m, 2) == 24_576
    cfg = fam.arch_config(m)
    assert (cfg.num_experts, cfg.expert_range, cfg.experts_per_token) == \
        (128, (0, 8), 8)


def test_held_expert_bytes_match_the_weights():
    m = _tiny()
    params = cells.reference(m).make_params(m, 5)
    layers = params["layers"]
    held = sum(layers[k].nbytes for k in ("we_gate", "we_up", "we_down"))
    assert held == m["num_hidden_layers"] * fam.expert_params(m) * 4
    assert layers["router"].shape == (2, 64, 16)
    total = sum(x.size for x in jax.tree.leaves(params))
    assert total == fam.model_params(m)


@pytest.mark.parametrize("change", [
    {"hidden_actt": "silu"}, {"norm_topk_prob": False},
    {"mlp_only_layers": [0]}, {"decoder_sparse_step": 2},
    {"model_type": "qwen3"}, {"num_experts": 16},
    {"deployment": {"held_experts": [124, 8]}}, {"deployment": "one chip"},
    {"published": {}}])
def test_family_refuses_what_it_does_not_describe(change):
    m = {**_config(), **change}
    with pytest.raises(ValueError):
        fam.validate(m, cells.HARNESS_KEYS)


def test_moe_roofline_reads_a_known_value():
    m = _config()
    read = cells.reader("moe_roofline.backlog")
    steps = [[100] * 64, [100] * 64]
    # decode, 64 tokens: 32 rows; a 512-token prompt: 256 rows; both
    # bound by the 8 experts' 302.0 MB of weights at 819 GB/s
    weights = 8 * 3 * 4096 * 1536 * 2
    least = 12 * (2 * (weights + 2 * 32 * 4096 * 2)
                  + (weights + 2 * 256 * 4096 * 2)) / 819e9
    ctx = SimpleNamespace(
        op_time=lambda part: (36, 2 * least) if part == "grouped_expert_ffn"
        else (0, 0.0),
        steps=steps, admissions=[512], config=m, flops=fam,
        peaks=peaks.peaks("TPU v5 lite"))
    assert read(ctx) == pytest.approx(50.0)
    ctx.op_time = lambda part: (0, 0.0)
    assert read(ctx) is None


@pytest.mark.parametrize("trace", [False, True])
def test_tiny_cell_runs_correct(monkeypatch, trace):
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    moe = cells.load(CELL)
    cell = cells.Cell("tiny-moe.backlog", 1, _tiny(), {
        "arrival": "backlog", "queue_per_lane": 2, "deck": 8,
        "prompt_len": {"values": [16, 32], "weights": [1, 1]},
        "output_len": {"dist": "loguniform", "min": 4, "max": 16},
        "temperature": 0.0, "check_tokens": 40}, moe.end_to_end,
        moe.per_layer)
    result, det = run.run_cell(cell, 2**31 + 3, 2.0, trace, log=lambda s: 0)
    assert result["correct"], result["checks"]
    assert det["tokens_compared"] >= 40
    if trace:
        # held rows per expert call: 1 in decode (4 lanes x 4 / 16),
        # more in prefill
        assert result["metrics"]["moe_rows_per_expert.backlog"]["value"] > 1
    else:
        assert set(result["metrics"]) == {"out_tok_s", "setup_s"}


def test_control_is_not_correct():
    m = _tiny()
    cell = cells.Cell("tiny-moe.backlog", 1, m, {
        "arrival": "backlog", "queue_per_lane": 2, "deck": 8,
        "prompt_len": {"values": [16, 32], "weights": [1, 1]},
        "output_len": {"dist": "loguniform", "min": 4, "max": 16},
        "temperature": 0.0, "check_tokens": 40}, [], [])
    result, det = run.run_cell(cell, 4, 1.0, False, control=True,
                               log=lambda s: 0)
    assert result["correct"]
    assert det["control_correct"] is False
