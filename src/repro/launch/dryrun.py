"""Multi-pod dry-run: lower + compile every (arch x shape) on the
production mesh, extract roofline terms.  No real allocation — inputs are
ShapeDtypeStructs; the 512 placeholder devices exist only here.

    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all            # 40 pairs, single-pod
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod
"""
# The first two lines MUST run before any other import (jax locks the
# device count at first init):
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import math
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp

from repro import models
from repro.configs.base import SHAPES, get_config, list_configs
from repro.launch import sharding as shd
from repro.launch import hlo_costs
from repro.launch.hlo_analysis import analyze_collectives, total_wire_bytes
from repro.launch.mesh import make_production_mesh
from repro.models import common as cm
from repro.optim.adamw import AdamW, cosine_schedule
from repro.sharding_hints import axis_rules

# TPU v5e hardware constants (per chip) — shared with the serving
# roofline accountant so dryrun estimates and live MBU/MFU gauges are
# anchored to the same peaks.
HW = hlo_costs.HW_PEAKS[hlo_costs.V5E]

ARCHS = [
    "rwkv6-3b", "whisper-medium", "qwen3-8b", "chameleon-34b",
    "tinyllama-1.1b", "qwen3-0.6b", "qwen3-moe-235b-a22b",
    "recurrentgemma-9b", "llama3-8b", "granite-moe-3b-a800m",
]


def build_step(cfg, shape, rules, mesh):
    """Returns (fn, arg_structs, in_shardings)."""
    mod = models.get_module(cfg)
    window = models.effective_window(cfg, shape)
    template = models.param_template(cfg)
    pdtype = jnp.bfloat16
    pstruct = cm.param_struct(template, pdtype)
    pshard = shd.param_shardings(template, rules, mesh)
    specs = models.input_specs(cfg, shape)
    bstruct = specs["batch"]
    bshard = shd.struct_shardings(bstruct, specs["batch_axes"], rules, mesh)
    rep = shd.replicated(mesh)

    if shape.kind == "train":
        opt = AdamW(lr=cosine_schedule(3e-4, 100, 10_000))
        f32s = lambda tree: jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), tree)
        ostruct = {"step": jax.ShapeDtypeStruct((), jnp.int32),
                   "m": f32s(pstruct), "v": f32s(pstruct)}
        oshard = {"step": rep, "m": pshard, "v": pshard}

        def step(params, opt_state, batch):
            from repro.optim.adamw import AdamWState
            st = AdamWState(opt_state["step"], opt_state["m"],
                            opt_state["v"])
            (loss, metrics), grads = jax.value_and_grad(
                lambda p: mod.loss_fn(cfg, p, batch, window=window),
                has_aux=True)(params)
            params, st, om = opt.update(grads, st, params)
            return params, {"step": st.step, "m": st.m, "v": st.v}, loss

        return (step, (pstruct, ostruct, bstruct),
                (pshard, oshard, bshard))

    if shape.kind == "prefill":
        cl = models.cache_len(cfg, shape)

        def step(params, batch):
            return mod.prefill(cfg, params, window=window, cache_len=cl,
                               **batch)

        return step, (pstruct, bstruct), (pshard, bshard)

    # decode
    cstruct = specs["cache"]
    cshard = shd.struct_shardings(cstruct, specs["cache_axes"], rules, mesh)

    def step(params, token, cache, pos):
        return mod.decode_step(cfg, params, token, cache, pos,
                               window=window)

    return (step, (pstruct, bstruct["token"], cstruct, specs["pos"]),
            (pshard, bshard["token"], cshard, rep))


def dryrun(arch: str, shape_name: str, *, multi_pod: bool = False,
           optimized: bool = False, save_dir=None, verbose: bool = True):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    rules = shd.rules_for_pair(arch, shape_name, shape.kind,
                               multi_pod=multi_pod, optimized=optimized)
    mesh_shape = rules.pop("_mesh_shape", None)
    mesh = make_production_mesh(multi_pod=multi_pod, shape=mesh_shape)
    chips = math.prod(mesh.devices.shape)
    t0 = time.time()
    with axis_rules(rules, mesh):
        fn, structs, shardings = build_step(cfg, shape, rules, mesh)
        with jax.set_mesh(mesh):
            lowered = jax.jit(fn, in_shardings=shardings).lower(*structs)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    cost = hlo_costs.xla_cost_analysis(compiled)
    hlo = compiled.as_text()
    # trip-count-aware analysis (cost_analysis counts while bodies ONCE —
    # every model scans over layers, so it understates by ~num_layers)
    hc = hlo_costs.analyze(hlo, chips)
    colls = hc["collectives"]
    wire = hc["wire_bytes"]

    flops_dev = float(hc["flops"])          # MXU dot/conv flops, per device
    bytes_dev = float(hc["hbm_bytes"])      # fusion-boundary bytes, per dev
    xla_flops = float(cost.get("flops", 0.0))       # recorded for reference
    xla_bytes = float(cost.get("bytes accessed", 0.0))
    compute_s = flops_dev / HW["peak_flops"]
    memory_s = bytes_dev / HW["hbm_bw"]
    coll_s = wire / HW["ici_bw"]
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": coll_s}
    bottleneck = max(terms, key=terms.get)

    n_active = get_config(arch).active_param_count() \
        if cfg.is_moe else cfg.param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind == "train"
                                   else (shape.seq_len
                                         if shape.kind == "prefill" else 1))
    mult = 6 if shape.kind == "train" else 2
    model_flops = mult * n_active * tokens
    hlo_flops_global = flops_dev * chips
    useful = model_flops / hlo_flops_global if hlo_flops_global else 0.0

    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "chips": chips,
        "optimized": optimized,
        "kind": shape.kind,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "wire_bytes_per_device": wire,
        "xla_cost_analysis": {"flops": xla_flops, "bytes": xla_bytes,
                              "note": "loop bodies counted once by XLA"},
        "hlo_warnings": hc["warnings"],
        "collectives": colls,
        "memory_analysis": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        } if mem is not None else None,
        "roofline": {
            **{k: float(v) for k, v in terms.items()},
            "bottleneck": bottleneck.replace("_s", ""),
            "model_flops": model_flops,
            "useful_flops_ratio": useful,
        },
    }
    if verbose:
        ma = result["memory_analysis"] or {}
        print(f"{arch:>22s} {shape_name:>12s} {result['mesh']:>8s} "
              f"{'OPT' if optimized else 'base'} "
              f"compute={compute_s*1e3:9.3f}ms mem={memory_s*1e3:9.3f}ms "
              f"coll={coll_s*1e3:9.3f}ms -> {result['roofline']['bottleneck']:10s} "
              f"useful={useful:5.1%} args={_fmt(ma.get('argument_bytes'))} "
              f"temp={_fmt(ma.get('temp_bytes'))} "
              f"(compile {t_compile:.0f}s)")
    if save_dir:
        save_dir = pathlib.Path(save_dir)
        save_dir.mkdir(parents=True, exist_ok=True)
        tag = "opt" if optimized else "base"
        fp = save_dir / f"{arch}__{shape_name}__{result['mesh']}__{tag}.json"
        fp.write_text(json.dumps(result, indent=1))
    return result


def _fmt(b):
    if b is None:
        return "   n/a"
    return f"{b/2**30:5.2f}G"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS)
    ap.add_argument("--shape", choices=sorted(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--optimized", action="store_true",
                    help="apply PERF_OVERRIDES sharding rules")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    pairs = []
    if args.all:
        pairs = [(a, s) for a in ARCHS for s in SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        pairs = [(args.arch, args.shape)]

    failures = []
    for arch, shape in pairs:
        try:
            dryrun(arch, shape, multi_pod=args.multi_pod,
                   optimized=args.optimized, save_dir=args.out)
        except Exception as e:  # noqa: BLE001 — report, keep sweeping
            failures.append((arch, shape, repr(e)))
            print(f"{arch:>22s} {shape:>12s} FAILED: {e}")
            traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print(f"\nall {len(pairs)} dry-runs compiled OK")


if __name__ == "__main__":
    main()
