import json

import pytest

import cells
import peaks
from cells import BENCH


def _config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


flops = cells.family(_config("qwen3-0.6b"))


def test_qwen3_8b_layer_parameters():
    # hf:Qwen/Qwen3-8B: 192.9 M per layer, 8.19 B in all (36 layers)
    m = {**_config("qwen3-8b-pp3"), "num_hidden_layers": 36}
    assert flops.layer_params(m) == 192_946_432
    assert flops.model_params(m) == pytest.approx(8.19e9, rel=1e-3)


def test_qwen3_0_6b_parameters_at_head_dim_128():
    m = _config("qwen3-0.6b")
    assert m["head_dim"] == 128
    assert flops.model_params(m) == pytest.approx(596e6, rel=1e-3)


def test_kv_bytes_per_token():
    assert flops.kv_bytes_per_token(_config("qwen3-8b-pp3"), 2) == 49_152
    assert flops.kv_bytes_per_token(_config("qwen3-0.6b"), 2) == 114_688


def test_prefill_counts_causal_attention_and_one_head_row():
    m = _config("qwen3-0.6b")
    one = flops.prefill_flops(m, 1)
    assert one == flops.decode_flops(m, [1])
    two = flops.prefill_flops(m, 2)
    # the second token adds its projections and two keys of attention,
    # and no second row of the output head
    assert two - 2 * one == flops.attn_flops(m, 1) \
        - 2 * m["hidden_size"] * m["vocab_size"]


def test_unknown_device_kind_raises():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


@pytest.mark.parametrize("change", [{"hidden_actt": "silu"},
                                    {"hidden_act": "gelu"},
                                    {"attention_bias": True},
                                    {"use_sliding_window": True}])
def test_family_refuses_what_it_does_not_describe(change):
    m = {**_config("qwen3-8b-pp3"), **change}
    with pytest.raises(ValueError):
        flops.validate(m, cells.HARNESS_KEYS)


def test_every_configuration_is_described_by_its_family():
    for path in (BENCH / "configs").glob("*.json"):
        m = json.loads(path.read_text())
        cells.family(m).validate(m, cells.HARNESS_KEYS)


def test_unknown_family_raises():
    with pytest.raises(KeyError):
        cells.family({**_config("qwen3-0.6b"), "model_type": "rwkv6"})
