"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --reduced --steps 200 --batch 8 --seq 128 --publish store/

Runs a real data-parallel training loop over a ``data`` mesh (default:
every visible device; the parameters are replicated and the global batch
is split along it), checkpointing into the model store so the serving
path can load the result — the paper's train-once / reuse-everywhere
loop closed end to end.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro import models
from repro.configs.base import get_config, reduced as reduce_cfg
from repro.data.pipeline import DataConfig, SyntheticLM, shard_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.optim.adamw import AdamW, cosine_schedule


def make_train_step(cfg, opt):
    mod = models.get_module(cfg)

    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: mod.loss_fn(cfg, p, batch), has_aux=True)(params)
        params, opt_state, om = opt.update(grads, opt_state, params)
        metrics = dict(metrics)
        metrics.update(om)
        return params, opt_state, metrics

    return jax.jit(train_step, donate_argnums=(0, 1))


def train(arch: str, *, steps: int = 100, batch: int = 8, seq: int = 128,
          lr: float = 3e-4, warmup: int = 20, use_reduced: bool = True,
          publish_to=None, log_every: int = 10, seed: int = 0, mesh=None):
    """Train ``arch`` for ``steps`` steps; ``mesh`` (default
    :func:`make_host_mesh`) carries the data parallelism."""
    cfg = get_config(arch)
    if use_reduced:
        cfg = reduce_cfg(cfg)
    mesh = mesh if mesh is not None else make_host_mesh()
    replicated = NamedSharding(mesh, PartitionSpec())
    key = jax.random.PRNGKey(seed)
    params = jax.device_put(models.init_params(cfg, key), replicated)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    opt = AdamW(lr=cosine_schedule(lr, warmup, steps))
    opt_state = jax.device_put(opt.init(params), replicated)
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                                  global_batch=batch, seed=seed))
    step_fn = make_train_step(cfg, opt)

    print(f"training {cfg.name} ({n_params/1e6:.1f}M params) on "
          f"{mesh.size} device(s), {steps} steps "
          f"batch={batch} seq={seq}")
    losses = []
    t0 = time.perf_counter()
    for step in range(steps):
        raw = data.batch(step)
        b = shard_batch(
            {k: v for k, v in raw.items()}, mesh, batch_axes=("data",))
        if cfg.family == "audio":
            b["frames"] = jnp.zeros((batch, cfg.encoder_seq, cfg.d_model),
                                    jnp.float32)
        params, opt_state, metrics = step_fn(params, opt_state, b)
        loss = float(metrics["loss"])
        losses.append(loss)
        if step % log_every == 0 or step == steps - 1:
            dt = time.perf_counter() - t0
            tok_s = batch * seq * (step + 1) / dt
            print(f"step {step:5d}  loss {loss:7.4f}  {tok_s:9.0f} tok/s")
    assert np.isfinite(losses[-1]), "training diverged"

    if publish_to:
        from repro.checkpoint.ckpt import publish_checkpoint
        from repro.core.modelstore import ModelStore
        store = ModelStore(publish_to)
        rec = publish_checkpoint(
            store, cfg.name, cfg, params,
            metadata={"steps": steps, "final_loss": losses[-1]})
        print(f"published {rec.name}:{rec.version} -> {rec.path}")
    return params, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--full", action="store_true",
                    help="full config (default: reduced smoke variant)")
    ap.add_argument("--publish", default=None, metavar="STORE_DIR")
    args = ap.parse_args()
    enable_compile_cache()
    _, losses = train(args.arch, steps=args.steps, batch=args.batch,
                      seq=args.seq, lr=args.lr, use_reduced=not args.full,
                      publish_to=args.publish)
    print(f"loss {losses[0]:.4f} -> {losses[-1]:.4f} "
          f"(delta {losses[0] - losses[-1]:+.4f})")


if __name__ == "__main__":
    main()
