"""Bring-up smoke test: drive the system's main paths once on the chip.

    python chip_smoke.py             # one chip: the serving path
    python chip_smoke.py --chips 4   # four chips: the trainer's
                                     # data-parallel path, nothing else

One chip: publish ``tinyllama-1.1b`` at its published widths (f32
random weights from ``--seed``) into a fresh temporary model store, load
it through MultiModelServer -> ServingEngine ->
ContinuousBatchingScheduler with the decode backend left on auto, and
serve 8 requests (prompts of 32-512 tokens, 32 new tokens each, cache
length 1024, 8 lanes) with the ring KV cache, then the same requests with
``kv_layout="paged"`` and with ``kv_dtype="int8"``.  Every variant is
compared with the same requests served with ``attn_backend="ref"``: the
first 8 greedy tokens of every request must match (both runs at float32
matmul precision).  The run also checks that decode resolved to the
Pallas kernel, that kernels compile instead of running in interpret
mode, and that the compiled decode step holds a ``tpu_custom_call``.

Four chips: ``launch.train.train`` on full-width ``qwen3-0.6b`` for 5
steps (global batch 8, seq 256) over a 4-way ``data`` mesh, then the same
steps on a one-device mesh; the batch must be split 4 ways and the
losses must agree.

With no TPU the script exits non-zero before any phase runs; it never
falls back to the CPU.  Any failed check raises, so the exit code is
non-zero.  The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import tempfile
import time
from typing import Dict, List, Optional, Sequence

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import models  # noqa: E402
from repro.core.modelstore import ModelStore  # noqa: E402
from repro.core.ops import resolve_decode_backend  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import ensure_model  # noqa: E402
from repro.serving.engine import (MultiModelServer, Request,  # noqa: E402
                                  ServingEngine)

SERVE_ARCH = "tinyllama-1.1b"
TRAIN_ARCH = "qwen3-0.6b"
# 8 requests over 32-512 prompt tokens; 4 distinct lengths keep the
# prefill compiles (one per length) to four per cache variant
PROMPT_LENS = (32, 512, 128, 256, 512, 32, 256, 128)
MAX_NEW = 32
CHECK_TOKENS = 8
CACHE_LEN = 1024
MAX_BATCH = 8
# (name, engine options); ring first so the variants reuse its compiles
VARIANTS = (("ring", {}),
            ("paged", {"kv_layout": "paged"}),
            ("int8", {"kv_dtype": "int8"}))
# data-parallel loss agreement: both runs at float32 matmul precision
# differ only in reduction order (per-device partial sums + all-reduce),
# which f32 keeps near 1e-6 relative; AdamW's normalisation can amplify
# that on near-zero gradients by at most lr per step, so 1e-3 of the
# loss leaves two orders of headroom while still catching a wrong split
LOSS_RTOL = 1e-3
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling while the
    ``with`` block runs (persistent-cache hits count only their lookup)."""

    def __init__(self):
        self.seconds = 0.0

    def _on_event(self, event: str, secs: float, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += secs

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_event)
        return False


def require_tpu() -> Dict[str, object]:
    """The device summary as JAX reports it, or exit non-zero when JAX
    finds no TPU."""
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {info['platform']!r})"
              f"; this script does not fall back to the CPU",
              file=sys.stderr)
        raise SystemExit(2)
    return info


# -- serving ------------------------------------------------------------------


def make_requests(vocab: int, seed: int,
                  prompt_lens: Sequence[int] = PROMPT_LENS,
                  max_new: int = MAX_NEW) -> List[Request]:
    rng = np.random.default_rng(seed)
    return [Request(uid=i, prompt=[int(t) for t in rng.integers(1, vocab, n)],
                    max_new_tokens=max_new)
            for i, n in enumerate(prompt_lens)]


def load_server(store_dir, *, full: bool = True, seed: int = 0,
                cache_len: int = CACHE_LEN, max_batch: int = MAX_BATCH):
    """Publish ``SERVE_ARCH`` into a fresh store and open a server on it.
    Returns ``(server, engine)``: the engine the server built for the
    model, attention backend on auto."""
    store = ModelStore(store_dir)
    ensure_model(store, SERVE_ARCH, full=full, seed=seed)
    server = MultiModelServer(store, max_resident=1, max_batch=max_batch,
                              cache_len=cache_len)
    return server, server._engine(SERVE_ARCH)


def _top2_gap(cfg, params, tokens: List[int]) -> float:
    """Gap between the two largest next-token logits of a plain forward
    pass over ``tokens``."""
    mod = models.get_module(cfg)
    logits = mod.forward(cfg, params, jnp.asarray([tokens], jnp.int32))
    top = jnp.sort(logits[0, -1])[-2:]
    return float(top[1] - top[0])


def compare_greedy(name: str, engine: ServingEngine, requests, got, want,
                   n: int = CHECK_TOKENS) -> None:
    """The first ``n`` tokens of every request in ``got`` must equal
    ``want``; on a mismatch report the reference's top-2 logit gap at
    the first step that differs, then fail."""
    for r, g, w in zip(requests, got, want):
        if g[:n] == w[:n]:
            continue
        t = next(i for i, (a, b) in enumerate(zip(g[:n], w[:n])) if a != b)
        gap = _top2_gap(engine.cfg, engine.params, list(r.prompt) + w[:t])
        raise SmokeFailure(
            f"{name}: request {r.uid} (prompt {len(r.prompt)}) differs from "
            f"ref at token {t}: {g[:n]} vs {w[:n]}; ref top-2 logit gap "
            f"there {gap:.6g}")


def check_device_path(name: str, resolved: str, hlo: str) -> None:
    """On the chip: auto decode resolved to a Pallas backend, kernels
    compile instead of running in interpret mode, and the compiled decode
    step holds a Mosaic kernel."""
    check(resolved in ("pallas", "paged", "pallas_q8", "paged_q8"),
          f"{name}: auto decode resolved to {resolved!r}, not pallas")
    check(kops._interpret(None) is False,
          f"{name}: kernels would run in interpret mode")
    check("tpu_custom_call" in hlo,
          f"{name}: no tpu_custom_call in the compiled decode step")


def serve_variant(server: MultiModelServer, base: ServingEngine, name: str,
                  options: Dict[str, object], *, seed: int = 0,
                  prompt_lens: Sequence[int] = PROMPT_LENS,
                  max_new: int = MAX_NEW) -> Dict[str, object]:
    """Serve the requests with ``options`` on the auto backend and again
    on ``ref``, check the device path, and compare greedy tokens."""
    vocab = base.cfg.vocab_size
    with CompileClock() as clock, jax.default_matmul_precision("float32"):
        if options:
            engine = ServingEngine(base.cfg, base.params,
                                   **{**server.engine_kw, **options})
            reqs = make_requests(vocab, seed, prompt_lens, max_new)
            engine.generate_batch(reqs)
        else:
            engine = base
            reqs = make_requests(vocab, seed, prompt_lens, max_new)
            server.serve(reqs, model=SERVE_ARCH)
        sched = engine.scheduler()
        resolved = resolve_decode_backend(
            sched.attn_backend, quantized=sched.kv_dtype == "int8",
            paged=sched.kv_layout == "paged")
        hlo = sched._step_fn.lower(sched.params, sched.state).compile() \
            .as_text()
        ref = ServingEngine(base.cfg, base.params,
                            **{**server.engine_kw, **options,
                               "attn_backend": "ref"})
        ref_reqs = make_requests(vocab, seed, prompt_lens, max_new)
        ref.generate_batch(ref_reqs)
    got = [r.output for r in reqs]
    want = [r.output for r in ref_reqs]
    custom_call = "tpu_custom_call" in hlo
    print(f"[{name}] decode backend {resolved}, tpu_custom_call in compiled "
          f"decode step: {custom_call}, compile {clock.seconds:.1f}s "
          f"(pallas+ref), served {sum(map(len, got))} tokens "
          f"(+{sum(map(len, want))} ref)", flush=True)
    check(all(len(o) == max_new for o in got + want),
          f"{name}: not every request produced {max_new} tokens")
    check_device_path(name, resolved, hlo)
    compare_greedy(name, engine, reqs, got, want)
    print(f"[{name}] first {CHECK_TOKENS} greedy tokens of all "
          f"{len(reqs)} requests match ref", flush=True)
    return {"variant": name, "backend": resolved, "compile_s": clock.seconds,
            "tokens": sum(map(len, got)), "tpu_custom_call": custom_call}


def run_serving(store_dir, *, full: bool = True, seed: int = 0,
                prompt_lens: Sequence[int] = PROMPT_LENS,
                max_new: int = MAX_NEW, cache_len: int = CACHE_LEN,
                max_batch: int = MAX_BATCH) -> List[Dict[str, object]]:
    t0 = time.perf_counter()
    server, base = load_server(store_dir, full=full, seed=seed,
                               cache_len=cache_len, max_batch=max_batch)
    cfg = base.cfg
    print(f"model {cfg.name}: {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.num_heads}/{cfg.num_kv_heads} heads, "
          f"head_dim {cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}, params {cfg.param_count() / 1e9:.3f}B f32; "
          f"published and loaded in {time.perf_counter() - t0:.1f}s",
          flush=True)
    return [serve_variant(server, base, name, options, seed=seed,
                          prompt_lens=prompt_lens, max_new=max_new)
            for name, options in VARIANTS]


# -- data-parallel training ---------------------------------------------------


def run_data_parallel(n: int = 4, *, full: bool = True, steps: int = 5,
                      batch: int = 8, seq: int = 256,
                      seed: int = 0) -> Dict[str, object]:
    """``train`` over an ``n``-way data mesh, then over one device; the
    batch must split ``n`` ways and the losses must agree."""
    from repro.configs.base import get_config
    from repro.data.pipeline import DataConfig, SyntheticLM, shard_batch
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import train

    devs = jax.devices()
    check(len(devs) >= n, f"need {n} devices, have {len(devs)}")
    mesh_n = make_host_mesh(devices=devs[:n])
    mesh_1 = make_host_mesh(devices=devs[:1])
    vocab = get_config(TRAIN_ARCH).vocab_size
    probe = shard_batch(SyntheticLM(DataConfig(vocab, seq, batch, seed))
                        .batch(0), mesh_n, batch_axes=("data",))["tokens"]
    shards = {s.device: s.data.shape for s in probe.addressable_shards}
    print(f"batch {probe.shape} over {len(shards)} devices, shard shapes "
          f"{sorted(set(shards.values()))}", flush=True)
    check(len(shards) == n and all(s == (batch // n, seq)
                                   for s in shards.values()),
          f"batch is not split {n} ways: {shards}")
    losses = {}
    with jax.default_matmul_precision("float32"):
        for label, mesh in ((f"{n}-way", mesh_n), ("1-device", mesh_1)):
            with CompileClock() as clock:
                t0 = time.perf_counter()
                losses[label] = train(
                    TRAIN_ARCH, steps=steps, batch=batch, seq=seq,
                    use_reduced=not full, log_every=1, seed=seed,
                    mesh=mesh)[1]
            print(f"[{label}] {steps} steps in "
                  f"{time.perf_counter() - t0:.1f}s, compile "
                  f"{clock.seconds:.1f}s, losses {losses[label]}",
                  flush=True)
    a = np.asarray(losses[f"{n}-way"])
    b = np.asarray(losses["1-device"])
    rel = float(np.max(np.abs(a - b) / np.abs(b)))
    print(f"max relative loss difference {rel:.3g} (limit {LOSS_RTOL})",
          flush=True)
    check(bool(np.isfinite(a).all()) and rel <= LOSS_RTOL,
          f"{n}-way losses {a.tolist()} disagree with 1-device "
          f"{b.tolist()}")
    return {"losses": losses, "max_rel_diff": rel}


# -- entry point --------------------------------------------------------------


def main(argv: Optional[Sequence[str]] = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data-parallel trainer phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and requests")
    args = ap.parse_args(argv)
    info = require_tpu()
    check(info["count"] >= args.chips,
          f"--chips {args.chips} but JAX sees {info['count']} device(s)")
    cache_dir = enable_compile_cache()
    print(f"jax {jax.__version__}, device {info['kind']} x{info['count']}, "
          f"compile cache {cache_dir}", flush=True)
    if args.chips == 4:
        run_data_parallel(4, seed=args.seed)
    else:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_store_") as d:
            run_serving(d, seed=args.seed)
    print(json.dumps({"ok": True, "device": info}))


if __name__ == "__main__":
    main()
