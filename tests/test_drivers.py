"""End-to-end driver smoke: train loop learns, serve driver round-trips,
perf-override wiring resolves."""
import jax
import numpy as np
import pytest

from repro.launch import sharding as shd


def test_train_driver_learns(tmp_path):
    from repro.launch.train import train
    params, losses = train("qwen3-0.6b", steps=25, batch=4, seq=64,
                           publish_to=str(tmp_path), log_every=100)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] - 0.2, (losses[0], losses[-1])
    # published into the store
    from repro.core.modelstore import ModelStore
    assert "qwen3-0.6b" in ModelStore(tmp_path).list_models()


def test_serve_driver_bootstrap(tmp_path):
    from repro.core.modelstore import ModelStore
    from repro.launch.serve import ensure_model
    store = ModelStore(tmp_path)
    ensure_model(store, "tinyllama-1.1b")
    ensure_model(store, "tinyllama-1.1b")       # idempotent
    assert store.list_models() == {"tinyllama-1.1b": ["v1"]}


def test_perf_overrides_resolve():
    base = shd.rules_for_pair("qwen3-moe-235b-a22b", "train_4k", "train")
    assert "moe_impl" not in base
    opt = shd.rules_for_pair("qwen3-moe-235b-a22b", "train_4k", "train",
                             optimized=True)
    assert opt["moe_impl"] == "a2a"
    assert opt["tp_ff"] is None
    g = shd.rules_for_pair("granite-moe-3b-a800m", "prefill_32k",
                           "prefill", optimized=True)
    assert g["_mesh_shape"] == (32, 8)


def test_mesh_shape_override():
    from repro.launch.mesh import make_production_mesh
    with pytest.raises(RuntimeError):
        make_production_mesh(shape=(32, 8))    # still needs 256 devices


def test_serve_driver_refuses_a_stale_config(tmp_path):
    """A model already in the store under another config is an error,
    never silently served as the config asked for."""
    from repro.core.modelstore import ModelStore
    from repro.launch.serve import ensure_model
    store = ModelStore(tmp_path)
    ensure_model(store, "tinyllama-1.1b")                 # reduced
    with pytest.raises(ValueError, match="different config"):
        ensure_model(store, "tinyllama-1.1b", full=True)
    assert store.list_models() == {"tinyllama-1.1b": ["v1"]}


def test_chip_smoke_serving_phases_on_cpu(tmp_path, monkeypatch):
    """chip_smoke's serving phases at reduced width, with the TPU checks
    off: store -> server -> ring, paged and int8 variants, each compared
    with its ref run."""
    import chip_smoke
    seen = []
    monkeypatch.setattr(chip_smoke, "check_device_path",
                        lambda name, resolved, hlo: seen.append(name))
    res = chip_smoke.run_serving(tmp_path, full=False,
                                 prompt_lens=(8, 24, 16, 8), max_new=8,
                                 cache_len=64, max_batch=4)
    assert seen == ["ring", "paged", "int8"]
    assert [r["variant"] for r in res] == ["ring", "paged", "int8"]
    assert [r["backend"] for r in res] == ["ref", "paged_ref", "ref_q8"]
    assert all(r["tokens"] == 4 * 8 for r in res)


def test_chip_smoke_compare_reports_the_logit_gap(tmp_path):
    """A greedy mismatch fails with the reference's top-2 logit gap."""
    import chip_smoke
    server, eng = chip_smoke.load_server(tmp_path, full=False, cache_len=64,
                                         max_batch=2)
    reqs = chip_smoke.make_requests(eng.cfg.vocab_size, 0, (8,), 4)
    want = [[1, 2, 3, 4]]
    with pytest.raises(chip_smoke.SmokeFailure, match="top-2 logit gap"):
        chip_smoke.compare_greedy("probe", eng, reqs, [[1, 2, 9, 4]], want)


def test_chip_smoke_main_refuses_the_cpu(capsys):
    """With no TPU, main exits non-zero before any phase and prints no
    result line."""
    import chip_smoke
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code != 0
    assert '"ok"' not in capsys.readouterr().out
