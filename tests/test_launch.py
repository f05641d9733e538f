"""Launch layer: sharding rule resolution, hint mechanics, HLO cost model,
and a miniature dry-run on a host-sized mesh (the 512-device production
dry-run is exercised by launch/dryrun.py, not under pytest — it must not
pollute the test process's device count)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

from repro.launch import hlo_costs, sharding as shd
from repro.launch.hlo_analysis import analyze_collectives, count_op
from repro.sharding_hints import logical_to_spec

# ---------------------------------------------------------------------------
# logical-axis -> PartitionSpec resolution
# ---------------------------------------------------------------------------


def test_logical_to_spec_basic():
    rules = {"batch": ("data",), "tp_ff": "model", "fsdp": "data"}
    spec = logical_to_spec(("batch", None, "tp_ff"), rules, (64, 128, 256))
    assert spec == PartitionSpec(("data",), None, "model")


def test_logical_to_spec_divisibility_guard():
    """A mapping that does not divide the dim is dropped, not an error —
    e.g. granite's 40-expert bank on a 16-way model axis."""
    from repro.sharding_hints import axis_rules
    mesh = jax.sharding.AbstractMesh((16,), ("model",))
    rules = {"experts": "model"}
    with axis_rules(rules, mesh):
        spec = logical_to_spec(("experts", None), rules, (40, 64))
        assert spec == PartitionSpec(None, None)
        spec2 = logical_to_spec(("experts", None), rules, (128, 64))
        assert spec2 == PartitionSpec("model", None)


def test_rules_for_kinds_differ():
    train = shd.rules_for("train")
    decode = shd.rules_for("decode")
    assert train["cache_seq"] is None
    assert decode["cache_seq"] == "model"
    multi = shd.rules_for("train", multi_pod=True)
    assert multi["batch"] == ("pod", "data")


def test_rules_overrides():
    r = shd.rules_for("train", overrides={"tp_ff": None, "seq": "model"})
    assert r["tp_ff"] is None
    assert r["seq"] == "model"


# ---------------------------------------------------------------------------
# HLO cost model — trip-count awareness is THE correctness property
# ---------------------------------------------------------------------------


def _compile_text(fn, *structs):
    return jax.jit(fn).lower(*structs).compile().as_text()


def test_hlo_costs_counts_plain_matmul():
    m, k, n = 128, 256, 64
    txt = _compile_text(lambda a, b: a @ b,
                        jax.ShapeDtypeStruct((m, k), jnp.float32),
                        jax.ShapeDtypeStruct((k, n), jnp.float32))
    r = hlo_costs.analyze(txt, 1)
    assert r["flops"] == pytest.approx(2 * m * k * n, rel=0.01)


def test_hlo_costs_scales_scan_body_by_trip_count():
    L, m, k = 9, 64, 128

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=L)
        return y

    txt = _compile_text(f, jax.ShapeDtypeStruct((m, k), jnp.float32),
                        jax.ShapeDtypeStruct((k, k), jnp.float32))
    r = hlo_costs.analyze(txt, 1)
    assert r["flops"] == pytest.approx(L * 2 * m * k * k, rel=0.01)


def test_hlo_costs_nested_scans_multiply():
    L1, L2, m = 3, 5, 32

    def f(x, w):
        def inner(c, _):
            return jnp.tanh(c @ w), None

        def outer(c, _):
            y, _ = jax.lax.scan(inner, c, None, length=L2)
            return y, None
        y, _ = jax.lax.scan(outer, x, None, length=L1)
        return y

    txt = _compile_text(f, jax.ShapeDtypeStruct((m, m), jnp.float32),
                        jax.ShapeDtypeStruct((m, m), jnp.float32))
    r = hlo_costs.analyze(txt, 1)
    assert r["flops"] == pytest.approx(L1 * L2 * 2 * m ** 3, rel=0.01)


def test_hlo_costs_memory_is_slice_aware():
    """Scanning slices of a big array must NOT count the full array per
    iteration."""
    L, m = 16, 64

    def f(xs, w):
        def body(c, x):
            return c + x @ w, None
        out, _ = jax.lax.scan(body, jnp.zeros((m, m)), xs)
        return out

    txt = _compile_text(f, jax.ShapeDtypeStruct((L, m, m), jnp.float32),
                        jax.ShapeDtypeStruct((m, m), jnp.float32))
    r = hlo_costs.analyze(txt, 1)
    full_per_iter = L * (L * m * m * 4)       # the overcount we must avoid
    assert r["hbm_bytes"] < 0.7 * full_per_iter


def test_hlo_costs_xla_comparison():
    """Direct demonstration that XLA's cost_analysis undercounts loops and
    our analyzer fixes it."""
    L, m = 12, 64

    def f(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=L)
        return y

    lowered = jax.jit(f).lower(jax.ShapeDtypeStruct((m, m), jnp.float32),
                               jax.ShapeDtypeStruct((m, m), jnp.float32))
    compiled = lowered.compile()
    xla_flops = hlo_costs.xla_cost_analysis(compiled).get("flops", 0.0)
    ours = hlo_costs.analyze(compiled.as_text(), 1)["flops"]
    assert ours == pytest.approx(L * 2 * m ** 3, rel=0.01)
    assert xla_flops < 0.5 * ours           # XLA counted the body once


# ---------------------------------------------------------------------------
# collective parsing (synthetic HLO lines)
# ---------------------------------------------------------------------------

HLO_SNIPPET = """
ENTRY %main (p: f32[256,512]) -> f32[256,512] {
  %p = f32[256,512]{1,0} parameter(0)
  %ag = f32[256,512]{1,0} all-gather(%p), channel_id=1, replica_groups=[2,4]<=[8], dimensions={1}
  %ar = f32[256,512]{1,0} all-reduce(%ag), channel_id=2, replica_groups=[1,8]<=[8], to_apply=%add
  ROOT %cp = f32[256,512]{1,0} collective-permute(%ar), source_target_pairs={{0,1},{1,0}}
}
"""


def test_analyze_collectives_ring_model():
    stats = analyze_collectives(HLO_SNIPPET, 8)
    nbytes = 256 * 512 * 4
    assert stats["all-gather"]["wire_bytes"] == pytest.approx(
        nbytes * 3 / 4)
    assert stats["all-reduce"]["wire_bytes"] == pytest.approx(
        2 * nbytes * 7 / 8)
    assert stats["collective-permute"]["wire_bytes"] == pytest.approx(nbytes)


def test_hlo_costs_collectives_match_ring_model():
    r = hlo_costs.analyze(HLO_SNIPPET, 8)
    nbytes = 256 * 512 * 4
    assert r["collectives"]["all-gather"]["wire_bytes"] == pytest.approx(
        nbytes * 3 / 4)
    assert r["wire_bytes"] > 0


# ---------------------------------------------------------------------------
# miniature end-to-end sharded train step on the host mesh
# ---------------------------------------------------------------------------


def test_sharded_train_step_compiles_on_host_mesh():
    from repro import models
    from repro.configs.base import get_config, reduced
    from repro.launch.mesh import make_host_mesh
    from repro.models import common as cm
    from repro.sharding_hints import axis_rules

    cfg = reduced(get_config("tinyllama-1.1b"))
    mesh = make_host_mesh()
    rules = shd.rules_for("train")
    template = models.param_template(cfg)
    with axis_rules(rules, mesh):
        pshard = shd.param_shardings(template, rules, mesh)
        pstruct = cm.param_struct(template, jnp.float32)
        mod = models.get_module(cfg)

        def step(params, batch):
            loss, _ = mod.loss_fn(cfg, params, batch)
            return loss

        bstruct = {"tokens": jax.ShapeDtypeStruct((4, 16), jnp.int32),
                   "labels": jax.ShapeDtypeStruct((4, 16), jnp.int32)}
        with jax.set_mesh(mesh):
            lowered = jax.jit(step, in_shardings=(pshard, None)).lower(
                pstruct, bstruct)
            compiled = lowered.compile()
    assert compiled.cost_analysis() is not None


def test_mesh_requires_enough_devices():
    from repro.launch.mesh import make_production_mesh
    with pytest.raises(RuntimeError):
        make_production_mesh()         # 1 CPU device < 256


_CACHE_PROBE = """
import json, sys
import jax, jax.numpy as jnp
from repro.launch import compile_cache
d = compile_cache.enable_compile_cache()
if sys.argv[1:] == ["compile"]:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.arange(8.0)).block_until_ready()
print(json.dumps({"dir": d, "config": jax.config.jax_compilation_cache_dir,
                  "default": str(compile_cache.DEFAULT_DIR)}))
"""


@pytest.mark.parametrize("placed", [True, False], ids=["env", "default"])
def test_compile_cache_dir_is_placed_from_outside(tmp_path, placed):
    """With JAX_COMPILATION_CACHE_DIR set the entries land there and the
    code sets nothing; unset, the cache is <checkout>/.jax_cache (checked
    without compiling, so the checkout stays clean)."""
    import json
    import os
    import pathlib
    import subprocess
    import sys
    root = pathlib.Path(__file__).resolve().parents[1]
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(root / "src"))
    if placed:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE] + (["compile"] if placed else []),
        env=env, capture_output=True, text=True, timeout=120,
        check=True).stdout.splitlines()[-1]
    got = json.loads(out)
    assert got["default"] == str(root / ".jax_cache")
    want = str(tmp_path) if placed else got["default"]
    assert got["dir"] == got["config"] == want
    if placed:
        assert any(tmp_path.iterdir()), "no cache entry written"
