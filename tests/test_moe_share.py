"""The expert layer at a chip's share of the bank, on the CPU at a tiny
size (d 64, 16 experts, top 4, expert width 32, float32).

The configuration is mapped by the benchmark's own family module
(``bench/families/qwen3_moe.py``) and compared with its plain reference
(``bench/reference/qwen3_moe.py``), both loaded by path as
``bench/cells.py`` loads them.  Logits are captured where the scheduler
samples them, so the comparison sees what the serving path served.
"""
import dataclasses
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import models
from repro.models import moe
from repro.runtime import scheduler as sched_mod
from repro.serving.engine import Request, ServingEngine

BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}", BENCH / kind / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("reference", "qwen3_moe")
FAM = _load("families", "qwen3_moe")

# the cell's keys at a tiny size: 4 of 16 experts held, experts 4-7
M = {"hidden_size": 64, "moe_intermediate_size": 32, "num_hidden_layers": 2,
     "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
     "vocab_size": 256, "num_experts": 4, "num_experts_per_tok": 4,
     "published": {"num_experts": 16}, "deployment": {"held_experts": [4, 4]},
     "tie_word_embeddings": False, "rope_theta": 1e6, "rms_norm_eps": 1e-6,
     "torch_dtype": "float32", "model_type": "qwen3_moe"}
CFG = FAM.arch_config(M)
PARAMS = REF.make_params(M, 3)
PROMPTS = [[int(t) for t in np.random.default_rng(i).integers(0, 256, n)]
           for i, n in enumerate((21, 9, 14, 30))]


def _held(cfg, first, count):
    return dataclasses.replace(cfg, held_experts=(first, count))


def _dense_layer(cfg, lp, x):
    """The uncut layer by its formula: every token's top-k experts of the
    whole bank, weights renormalised, each expert's SwiGLU summed."""
    probs = jax.nn.softmax(x @ lp["router"], axis=-1)
    top_p, top_e = jax.lax.top_k(probs, cfg.experts_per_token)
    top_p = top_p / top_p.sum(-1, keepdims=True)
    out = jnp.zeros_like(x)
    for e in range(cfg.num_experts):
        w = jnp.where(top_e == e, top_p, 0.0).sum(-1)[..., None]
        h = jax.nn.silu(x @ lp["we_gate"][e]) * (x @ lp["we_up"][e])
        out = out + w * (h @ lp["we_down"][e])
    return out


def _whole_bank_layer():
    cfg = _held(CFG, 0, CFG.num_experts)
    params = models.init_params(cfg, jax.random.PRNGKey(7))
    return cfg, jax.tree.map(lambda a: a[0], params["layers"])


def _share(lp, first, count):
    return {**lp, **{k: lp[k][first:first + count]
                     for k in ("we_gate", "we_up", "we_down")}}


def test_shares_add_up_to_the_uncut_layer():
    # float32 on both sides: the same products, summed in another order
    cfg, lp = _whole_bank_layer()
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 5, cfg.d_model))
    whole, stats = moe.moe_ffn_held(cfg, lp, x)
    parts = [moe.moe_ffn_held(_held(cfg, 4 * i, 4), _share(lp, 4 * i, 4), x)
             for i in range(4)]
    total = sum(p[0] for p in parts)
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(whole, _dense_layer(cfg, lp, x),
                               rtol=1e-5, atol=1e-6)
    # every (token, choice) pair is computed on exactly one share
    assert sum(int(p[1][0]) for p in parts) == int(stats[0]) == 15 * 4
    assert [int(p[1][1]) for p in parts] == [4] * 4


def test_concentrated_routing_drops_nothing():
    """A router that sends every token to the same experts: each held
    expert takes every token, and none is dropped."""
    cfg, lp = _whole_bank_layer()
    bias = jnp.zeros((cfg.num_experts,)).at[4:8].set(100.0)
    x = jax.random.normal(jax.random.PRNGKey(2), (8, 4, cfg.d_model))
    x = x.at[..., 0].set(1.0)        # a feature the router reads as bias
    lp = {**lp, "router": lp["router"].at[0].set(bias)}
    share = _held(cfg, 4, 4)
    out, stats = moe.moe_ffn_held(share, _share(lp, 4, 4), x)
    np.testing.assert_allclose(out, _dense_layer(cfg, lp, x),
                               rtol=1e-5, atol=1e-6)
    assert [int(s) for s in stats] == [32 * 4, 4, 32]


@pytest.mark.parametrize("dtype,rows,tile", [
    (jnp.float32, [3, 0, 9, 1], 16),
    (jnp.bfloat16, [20, 0, 33, 1], 32),
    (jnp.float32, [300, 5, 0, 130], 256)], ids=["f32", "bf16", "chunks"])
def test_grouped_kernel_matches_jnp(dtype, rows, tile):
    """The Pallas kernel (interpret mode) against its jnp oracle on every
    group's live rows, at layer 1 of a stack of 2: the same products in
    the same precision, so what is left is float32 rounding in another
    order, and in bfloat16 that rounding can move an output by one unit
    in its last place (2**-8 of it)."""
    from repro.kernels import ops as kops
    from repro.kernels.ref import grouped_ffn_ref
    e, d, f = 4, 128, 512
    rows = np.asarray(rows)
    blocks = -(-rows // tile)
    start = np.cumsum(blocks) - blocks
    n = (int(blocks.sum()) + 1) * tile
    live = np.zeros(n, bool)
    for g in range(e):
        live[start[g] * tile:start[g] * tile + rows[g]] = True
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jnp.where(live[:, None], jax.random.normal(ks[0], (n, d)), 0.0)
    w = [jax.random.normal(k, s) / np.sqrt(s[-2])
         for k, s in zip(ks[1:], [(2, e, d, f), (2, e, d, f), (2, e, f, d)])]
    args = [x.astype(dtype), *(a.astype(dtype) for a in w),
            jnp.asarray(start), jnp.asarray(rows), 1]
    got = kops.grouped_expert_ffn(*args, tile=tile,
                                  chunks=-(-int(rows.max()) // tile),
                                  interpret=True)
    want = grouped_ffn_ref(*args, tile=tile)
    rtol = 2.0 ** -8 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32)[live],
                               np.asarray(want, np.float32)[live],
                               rtol=rtol, atol=1e-5)


def _serve(prompts, max_new, *, max_batch=4, monkeypatch):
    """Serve ``prompts`` greedily on the paged path; returns each
    request's output and the logits sampled for it, in order."""
    seen = []

    def record(logits):
        seen.append(np.asarray(logits))

    sample = sched_mod._sample

    def sample_and_record(key, logits, temp):
        jax.debug.callback(record, logits, ordered=True)
        return sample(key, logits, temp)

    monkeypatch.setattr(sched_mod, "_sample", sample_and_record)
    eng = ServingEngine(CFG, PARAMS, max_batch=max_batch, cache_len=64,
                        kv_layout="paged", page_size=16)
    sched = eng.scheduler(max_new_cap=max_new)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        sched.submit(r)
    # a tick samples its admissions' first tokens (in order of arrival),
    # then one step of every lane: hand each request its rows
    rows = {r.uid: [] for r in reqs}
    slot_of = {}
    while sched.pending or any(s is not None for s in sched.slots):
        live = set(slot_of)
        n0 = len(seen)
        sched.tick()
        jax.effects_barrier()
        got = seen[n0:]
        slot_of.update({r.uid: i for i, r in enumerate(sched.slots)
                        if r is not None})
        for uid in sorted(set(slot_of) - live):
            rows[uid].append(got.pop(0)[0])
        for uid, slot in slot_of.items():
            if got and len(rows[uid]) < max_new:
                rows[uid].append(got[-1][slot])
    return {r.uid: (r.output, np.stack(rows[r.uid])) for r in reqs}


def test_logits_match_the_reference(monkeypatch):
    """After prefill and after each decode step through the paged cache,
    the served logits equal the reference's full forward pass.  Both
    sides are float32 (the cache too) and route the same experts; what is
    left is float32 rounding summed in other orders over two layers, some
    1e-6 on logits of order 1, so 1e-4 holds them, while one bfloat16
    rounding of the hidden state (relative 4e-3) would not."""
    out = _serve(PROMPTS[:2], 6, monkeypatch=monkeypatch)
    for uid, (served, got) in out.items():
        prompt = PROMPTS[uid]
        ref = REF.logits(M, PARAMS, prompt + served[:-1])
        want = ref[len(prompt) - 1:]
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)
        assert served == [int(t) for t in want.argmax(-1)]


def test_dropless_output_does_not_depend_on_neighbours(monkeypatch):
    """A request's logits are the same alone and beside other requests,
    at any batch size: no token of it is dropped for its neighbours'
    sake.  It comes last, on the last lane, whose (token, expert) pairs a
    capacity would drop first.  Rows are computed independently, so only
    float32 rounding of other matmul shapes is left (1e-5)."""
    alone = _serve(PROMPTS[:1], 6, max_batch=1, monkeypatch=monkeypatch)[0]
    for max_batch, others in ((4, []), (4, PROMPTS[1:]),
                              (16, (PROMPTS * 4)[1:])):
        prompts = others + PROMPTS[:1]
        got = _serve(prompts, 6, max_batch=max_batch,
                     monkeypatch=monkeypatch)[len(prompts) - 1]
        assert got[0] == alone[0]
        np.testing.assert_allclose(got[1], alone[1], atol=1e-5, rtol=0)


def test_routing_agrees_with_the_reference(monkeypatch, record_property):
    """Share of (token, layer) top-k sets on which the program's prefill
    and the reference differ.  Both route in float32 here, so a
    difference needs two router logits within rounding of each other:
    none is expected, and a few per cent would mean another rule."""
    seen = []
    route = moe._route

    def route_and_record(cfg, xf, router):
        top_p, top_e, aux = route(cfg, xf, router)
        jax.debug.callback(lambda e: seen.append(np.asarray(e)), top_e,
                           ordered=True)
        return top_p, top_e, aux

    monkeypatch.setattr(moe, "_route", route_and_record)
    differ = total = 0
    for prompt in PROMPTS:
        seen.clear()
        jax.block_until_ready(moe.prefill(
            CFG, PARAMS, jnp.asarray([prompt], jnp.int32), 64))
        jax.effects_barrier()
        want = REF.routes(M, PARAMS, prompt)                  # (L, S, k)
        got = np.stack(seen)
        assert got.shape == want.shape
        same = [set(a) == set(b) for a, b in zip(got.reshape(-1, 4),
                                                 want.reshape(-1, 4))]
        differ += len(same) - sum(same)
        total += len(same)
    share = differ / total
    record_property("routing_difference_share", share)
    print(f"routing sets that differ: {differ} of {total} ({share:.4f})")
    assert share <= 0.02
