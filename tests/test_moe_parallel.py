"""MoE implementation equivalence: the §Perf shard_map paths (a2a expert
parallelism, local replicated experts) must match the dense GSPMD baseline
bit-for-bit on the logits when capacity is high enough that neither path
drops tokens.

Runs in a SUBPROCESS with 8 forced host devices so the test process's own
device count stays 1 (the conftest invariant).
"""
import subprocess
import sys
import textwrap

import pytest

CODE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import get_config, reduced
    from repro import models
    from repro.launch import sharding as shd
    from repro.sharding_hints import axis_rules

    cfg = reduced(get_config("qwen3-moe-235b-a22b"))
    cfg = dataclasses.replace(cfg, capacity_factor=8.0)   # no drops
    mod = models.get_module(cfg)
    key = jax.random.PRNGKey(0)
    params = models.init_params(cfg, key)
    tokens = jax.random.randint(key, (4, 16), 0, cfg.vocab_size)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    outs = {}
    for impl, extra in [("dense", {}), ("a2a", {"tp_ff": None}),
                        ("local", {"experts": None, "tp_ff": None})]:
        rules = shd.rules_for("train", overrides={"moe_impl": impl, **extra})
        with axis_rules(rules, mesh):
            pshard = shd.param_shardings(models.param_template(cfg),
                                         rules, mesh)
            pp = jax.device_put(params, pshard)
            with jax.set_mesh(mesh):
                logits, aux = jax.jit(
                    lambda p, t: mod.forward(cfg, p, t))(pp, tokens)
        outs[impl] = np.asarray(logits, np.float32)
        assert np.isfinite(outs[impl]).all(), impl
    for impl in ("a2a", "local"):
        d = np.abs(outs[impl] - outs["dense"]).max()
        assert d < 2e-2, (impl, d)
    print("MOE_EQUIVALENCE_OK")
""")


@pytest.mark.slow
def test_moe_impls_equivalent_on_8_device_mesh():
    r = subprocess.run([sys.executable, "-c", CODE], capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "MOE_EQUIVALENCE_OK" in r.stdout
