"""Paged KV cache: block-table indirection, copy-on-write shared-prefix
reuse, refcount hygiene, and token parity with the ring layout.

The load-bearing guarantees:

  * paged backends are TOKEN-IDENTICAL to the ring backends under greedy
    decoding for every family (prefix sharing off — suffix-by-suffix
    prefill has different fp accumulation than chunked prefill, so the
    sharing path is checked for self-consistency instead),
  * the kernel/oracle pair agrees on arbitrarily fragmented,
    out-of-order page tables,
  * forking lanes off a shared prefix copy-on-writes — cached entries
    stay pristine and divergent lanes produce their solo outputs,
  * admit/retire cycles leak no pages (refcount/free-list invariant).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import models
from repro.configs.base import get_config, reduced
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.runtime.pagepool import GARBAGE_PAGE, PagePool
from repro.runtime.scheduler import ContinuousBatchingScheduler, Request

KEY = jax.random.PRNGKey(0)

# families with a paged path; rwkv6 (O(1) state, no KV) must fall back
PAGED_ARCHS = ["tinyllama-1.1b", "qwen3-moe-235b-a22b",
               "recurrentgemma-9b", "whisper-medium"]


@pytest.fixture(scope="module")
def family(request):
    cfg = reduced(get_config(request.param))
    return cfg, models.init_params(cfg, KEY)


def _sched(cfg, params, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("cache_len", 64)
    kw.setdefault("max_new_cap", 16)
    return ContinuousBatchingScheduler(cfg, params, **kw)


def _run(cfg, params, prompts, *, max_new=8, **kw):
    s = _sched(cfg, params, **kw)
    reqs = [Request(uid=i, prompt=list(p), max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        s.submit(r)
    s.run()
    return [r.output for r in reqs], s


# ---------------------------------------------------------------------------
# kernel-level: fragmented page tables
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("valid_kind", ["scalar", "ragged"])
@pytest.mark.parametrize("quantized", [False, True])
def test_paged_kernel_fragmented_out_of_order_pages(valid_kind, quantized):
    """The paged flash-decode kernel must match the gather-based oracle
    when lanes' pages are shuffled arbitrarily across the pool — the
    whole point of the block-table indirection — with per-lane or one
    shared valid length."""
    rng = np.random.default_rng(0)
    b, h, kvh, d, ps, w = 3, 8, 2, 32, 16, 4
    p = 1 + b * w + 3
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
    shape = (p, kvh, ps, d)
    sshape = (p, kvh, ps)
    # non-contiguous, interleaved, reverse-ordered physical pages
    perm = rng.permutation(np.arange(1, p))[:b * w].reshape(b, w)
    pt = jnp.asarray(perm, jnp.int32)
    valid = jnp.asarray(rng.integers(1, w * ps + 1, size=(b,)), jnp.int32)
    if valid_kind == "scalar":
        valid = valid[0]
    if quantized:
        k = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        v = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        ks = jnp.asarray(rng.uniform(0.01, 0.05, sshape), jnp.float32)
        vs = jnp.asarray(rng.uniform(0.01, 0.05, sshape), jnp.float32)
        got = kops.decode_attention_paged_q8(q, k, v, ks, vs, pt, valid)
        want = kref.decode_attention_paged_q8_ref(q, k, v, ks, vs, pt,
                                                  valid)
    else:
        k = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        v = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        got = kops.decode_attention_paged(q, k, v, pt, valid)
        want = kref.decode_attention_paged_ref(q, k, v, pt, valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_paged_gather_matches_ring_oracle_exactly():
    """The paged oracle is a pure memory reorder of the ring oracle:
    gathering pages back into ring layout must be bit-identical."""
    rng = np.random.default_rng(1)
    b, h, kvh, d, ps, w = 2, 4, 2, 16, 8, 3
    p = 1 + b * w
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
    pool_k = jnp.asarray(rng.standard_normal((p, kvh, ps, d)), jnp.float32)
    pool_v = jnp.asarray(rng.standard_normal((p, kvh, ps, d)), jnp.float32)
    pt = jnp.asarray(rng.permutation(np.arange(1, p)).reshape(b, w),
                     jnp.int32)
    valid = jnp.asarray([5, w * ps], jnp.int32)
    ring_k = kref.paged_gather(pool_k, pt)
    ring_v = kref.paged_gather(pool_v, pt)
    want = kref.decode_attention_ref(q, ring_k, ring_v, valid)
    got = kref.decode_attention_paged_ref(q, pool_k, pool_v, pt, valid)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# scheduler-level: token parity, COW, refcounts
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family", PAGED_ARCHS, indirect=True)
@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_paged_matches_ring_greedy(family, kv_dtype):
    """Greedy decode through the paged layout must reproduce the ring
    layout token-for-token (prefix sharing off isolates the layout)."""
    cfg, params = family
    rng = np.random.default_rng(11)
    prompts = [list(rng.integers(1, 100, size=n)) for n in (5, 12, 9, 17)]
    ring, _ = _run(cfg, params, prompts, kv_dtype=kv_dtype)
    paged, s = _run(cfg, params, prompts, kv_dtype=kv_dtype,
                    kv_layout="paged", page_size=16, prefix_sharing=False)
    assert s.kv_layout == "paged"
    assert ring == paged
    s.pool.leak_check()
    assert s.pool.available() == s.num_pages - 1   # all pages returned


def test_rwkv6_falls_back_to_ring():
    """No KV cache to page: requesting paged on rwkv6 silently keeps the
    ring layout and still generates."""
    cfg = reduced(get_config("rwkv6-3b"))
    params = models.init_params(cfg, KEY)
    outs, s = _run(cfg, params, [[3, 1, 4, 1, 5]], kv_layout="paged")
    assert s.kv_layout == "ring"
    assert s.free_slots().pages is None
    assert len(outs[0]) == 8


def test_prefix_hit_shares_pages_and_saves_prefill(tiny_sched_family):
    """N identical prompts: first admission is the only cold prefill;
    every later one maps the cached pages and prefill-computes one
    suffix token.  All outputs identical (greedy)."""
    cfg, params = tiny_sched_family
    common = list(np.random.default_rng(3).integers(1, 100, size=33))
    outs, s = _run(cfg, params, [common] * 5, max_new=6,
                   kv_layout="paged", page_size=16)
    assert all(o == outs[0] for o in outs)
    st = s.paged_stats()
    assert st["prefix_hits"] == 4
    assert st["prefill_tokens_saved"] == 4 * 32   # plen-1 per hit
    s.pool.leak_check()


def test_cow_fork_divergent_suffixes(tiny_sched_family):
    """Two prompts sharing a page-aligned prefix but with different
    tails: the second maps the shared pages, COWs on divergence, and
    each output equals its solo (no-sharing) run — shared pages never
    leak one lane's writes into another."""
    cfg, params = tiny_sched_family
    rng = np.random.default_rng(5)
    prefix = list(rng.integers(1, 100, size=32))       # 2 whole pages
    a = prefix + list(rng.integers(1, 100, size=7))
    b = prefix + list(rng.integers(100, 200, size=7))
    solo_a, _ = _run(cfg, params, [a], kv_layout="paged", page_size=16,
                     prefix_sharing=False)
    solo_b, _ = _run(cfg, params, [b], kv_layout="paged", page_size=16,
                     prefix_sharing=False)
    # sequential: a is admitted, decoded, retired; then b hits a's
    # registered prefix entries
    s = _sched(cfg, params, max_slots=1, kv_layout="paged", page_size=16)
    ra = Request(uid=0, prompt=a, max_new_tokens=8)
    rb = Request(uid=1, prompt=b, max_new_tokens=8)
    s.submit(ra)
    s.submit(rb)
    s.run()
    assert s.paged_stats()["prefix_hits"] == 1
    assert ra.output == solo_a[0]
    assert rb.output == solo_b[0]
    s.pool.leak_check()


def test_cow_keeps_cached_entry_pristine(tiny_sched_family):
    """A lane decoding past a shared partial page must COW it: a later
    admission of the same prompt still reproduces the original output."""
    cfg, params = tiny_sched_family
    prompt = list(np.random.default_rng(9).integers(1, 100, size=21))
    outs, s = _run(cfg, params, [prompt] * 3, max_new=10,
                   kv_layout="paged", page_size=16)
    st = s.paged_stats()
    assert all(o == outs[0] for o in outs)
    # 21 tokens -> pages [16][5..]; decodes write into the partial page,
    # which is shared with the registered entry -> at least one COW
    assert st["cow_copies"] >= 1
    s.pool.leak_check()


def test_no_page_leaks_across_admit_retire_cycles(tiny_sched_family):
    """Many admit/decode/retire cycles with mixed hits and misses: the
    refcount invariant holds throughout, and draining the prefix cache
    returns every page to the free list."""
    cfg, params = tiny_sched_family
    rng = np.random.default_rng(13)
    s = _sched(cfg, params, kv_layout="paged", page_size=16)
    for cycle in range(3):
        prompts = [list(rng.integers(1, 50, size=rng.integers(4, 30)))
                   for _ in range(3)]
        prompts.append(list(prompts[0]))               # guaranteed hit
        for i, p in enumerate(prompts):
            s.submit(Request(uid=cycle * 10 + i, prompt=p,
                             max_new_tokens=5))
        s.run()
        s.pool.leak_check()
        assert all(r is None for r in s.slots)
        assert (s._pt_host == GARBAGE_PAGE).all()      # rows cleared
    while s.pool.evict_one():
        pass
    s.pool.leak_check()
    assert s.pool.available() == s.num_pages - 1


def test_submit_rejects_on_pool_capacity(tiny_sched_family):
    """The paged submit guard replaces the ring cache_len bound: too-long
    prompts are rejected against the lane's PAGE capacity, a pool that
    cannot hold even one lane is rejected at construction, and an
    at-capacity prompt is accepted."""
    cfg, params = tiny_sched_family
    s = _sched(cfg, params, kv_layout="paged", page_size=16)
    with pytest.raises(ValueError, match="capacity"):
        s.submit(Request(uid=0, prompt=[1] * 80, max_new_tokens=4))
    # plen + max_new - 1 == capacity fits without wrapping the window
    s.submit(Request(uid=1, prompt=[1] * 61, max_new_tokens=4))
    s.run()
    with pytest.raises(ValueError, match="num_pages"):
        _sched(cfg, params, kv_layout="paged", page_size=16,
               num_pages=3)                  # < 1 garbage + 4 per lane


def test_admission_defers_under_pool_pressure(tiny_sched_family):
    """With a pool too small for two resident lanes, the second request
    queues until the first retires and frees its pages — deferral, not
    a crash."""
    cfg, params = tiny_sched_family
    s = _sched(cfg, params, kv_layout="paged", page_size=16,
               num_pages=1 + 5, prefix_sharing=False)
    for uid in range(2):
        s.submit(Request(uid=uid, prompt=[uid + 1] * 40,
                         max_new_tokens=8))             # 3 pages each
    s.run()
    for uid, r in enumerate(s.slots):
        assert r is None
    assert s.pool.available() == 5


def test_free_slots_reports_lanes_and_pages(tiny_sched_family):
    cfg, params = tiny_sched_family
    s = _sched(cfg, params, kv_layout="paged", page_size=16)
    free0 = s.free_slots()
    assert free0.lanes == 2 and free0.pages == s.num_pages - 1
    s.submit(Request(uid=0, prompt=[1] * 20, max_new_tokens=4))
    s.tick()
    free1 = s.free_slots()
    assert free1.lanes == 1 and free1.pages < free0.pages
    s.run()


def test_kv_bytes_resident_tracks_live_pages(tiny_sched_family):
    """Residency accounting: an idle paged scheduler holds only the
    bookkeeping arrays; admitting a short prompt adds a few pages —
    both strictly below the ring layout's full static allocation."""
    cfg, params = tiny_sched_family
    ring = _sched(cfg, params)
    paged = _sched(cfg, params, kv_layout="paged", page_size=16)
    idle = paged.kv_bytes_resident()
    assert idle < ring.kv_bytes_resident()
    paged.submit(Request(uid=0, prompt=[1, 2, 3], max_new_tokens=4))
    paged.tick()
    assert idle < paged.kv_bytes_resident() < ring.kv_bytes_resident()
    paged.run()


# ---------------------------------------------------------------------------
# PagePool unit behavior
# ---------------------------------------------------------------------------


def test_pagepool_alloc_free_refcount():
    pool = PagePool(6, 4)
    assert pool.available() == 5
    pages = pool.alloc(3)
    assert GARBAGE_PAGE not in pages
    assert pool.alloc(3) is None                      # only 2 left
    pool.ref(pages[0])
    pool.free(pages[0])
    assert pool.refcount[pages[0]] == 1               # still held
    for p in pages:
        pool.free(p)
    assert pool.available() == 5
    pool.leak_check()


def test_pagepool_prefix_lru_eviction():
    pool = PagePool(10, 4)
    a = pool.alloc(2)
    pool.prefix_register([1, 2, 3, 4, 5, 6, 7, 8], a)   # entries: a4, a8
    b = pool.alloc(2)
    pool.prefix_register([9, 9, 9, 9, 9, 9, 9, 9], b)   # entries: b4, b8
    for p in a + b:                                     # lanes retire
        pool.free(p)
    assert pool.available() == 5                        # entries hold pages
    hit = pool.prefix_lookup([1, 2, 3, 4, 5, 6, 7, 8, 77])
    assert hit is not None and hit.length == 8          # a8 moved to MRU
    assert pool.evict_one()                             # LRU head: a4
    assert pool.evict_one()                             # b4
    assert pool.evict_one()                             # b8 -> b pages free
    assert pool.available() == 7
    assert pool.prefix_lookup([9] * 8) is None          # b fully evicted
    assert pool.prefix_lookup([1, 2, 3, 4, 5, 6, 7, 8]) is not None
    assert pool.evict_one()                             # last: a8
    assert not pool.evict_one()
    assert pool.available() == 9
    pool.leak_check()


@pytest.fixture(scope="module")
def tiny_sched_family():
    cfg = reduced(get_config("tinyllama-1.1b"))
    return cfg, models.init_params(cfg, KEY)
