"""Per-kernel correctness: Pallas (interpret=True on CPU) vs pure-jnp
oracles in repro.kernels.ref, swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

from conftest import assert_close

KEY = jax.random.PRNGKey(42)


def rand(shape, dtype=jnp.float32, key=KEY, scale=1.0):
    return (scale * jax.random.normal(key, shape)).astype(dtype)


# ---------------------------------------------------------------------------
# matmul
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 384, 512),
                                   (64, 1024, 128), (300, 200, 100)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_shapes_dtypes(m, k, n, dtype):
    a, b = rand((m, k), dtype), rand((k, n), dtype)
    rtol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    assert_close(ops.matmul(a, b), ref.matmul_ref(a, b), rtol=rtol)


@pytest.mark.parametrize("act", ["none", "relu", "silu", "gelu"])
def test_matmul_fused_activation(act):
    a, b = rand((128, 256)), rand((256, 128))
    bias = rand((128,))
    assert_close(ops.matmul(a, b, bias, activation=act),
                 ref.matmul_ref(a, b, bias=bias, activation=act), rtol=1e-3)


def test_matmul_block_shapes():
    a, b = rand((512, 512)), rand((512, 512))
    want = ref.matmul_ref(a, b)
    for bm, bn, bk in [(128, 128, 128), (256, 256, 512), (512, 512, 512)]:
        assert_close(ops.matmul(a, b, block_m=bm, block_n=bn, block_k=bk),
                     want, rtol=1e-4)


# ---------------------------------------------------------------------------
# conv2d (the paper's flagship operator) — NCHW / OIHW, Caffe layout
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cin,cout,k,stride,pad", [
    (3, 16, 5, 1, 2),     # NIN conv1
    (16, 8, 1, 1, 0),     # NIN mlpconv 1x1
    (8, 8, 3, 1, 1),
    (8, 16, 3, 2, 0),     # strided
    (1, 4, 5, 1, 0),      # LeNet-style
])
def test_conv2d_vs_ref(cin, cout, k, stride, pad):
    x = rand((2, cin, 16, 16))
    w = rand((cout, cin, k, k), scale=0.2)
    b = rand((cout,))
    assert_close(ops.conv2d(x, w, b, stride=stride, pad=pad),
                 ref.conv2d_ref(x, w, b, stride=stride, pad=pad), rtol=1e-3)


def test_conv2d_fused_relu():
    x, w = rand((2, 4, 8, 8)), rand((8, 4, 3, 3))
    got = ops.conv2d(x, w, stride=1, pad=1, activation="relu")
    want = jax.nn.relu(ref.conv2d_ref(x, w, None, stride=1, pad=1))
    assert_close(got, want, rtol=1e-3)
    assert float(np.asarray(got).min()) >= 0.0


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_conv2d_dtypes(dtype):
    x = rand((1, 3, 12, 12), dtype)
    w = rand((6, 3, 3, 3), dtype, scale=0.2)
    rtol = 3e-2 if dtype == jnp.bfloat16 else 1e-3
    assert_close(ops.conv2d(x, w, pad=1),
                 ref.conv2d_ref(x, w, None, pad=1), rtol=rtol, atol=3e-2)


# ---------------------------------------------------------------------------
# pooling
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["max", "avg"])
@pytest.mark.parametrize("kernel,stride,pad", [(2, 2, 0), (3, 2, 1),
                                               (3, 1, 1), (8, 1, 0)])
def test_pool2d(mode, kernel, stride, pad):
    x = rand((2, 6, 16, 16))
    assert_close(
        ops.pool2d(x, mode=mode, kernel=kernel, stride=stride, pad=pad),
        ref.pool2d_ref(x, mode=mode, kernel=kernel, stride=stride, pad=pad),
        rtol=1e-5)


# ---------------------------------------------------------------------------
# softmax / elementwise
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(4, 10), (64, 1000), (128, 51865)])
def test_softmax_rows(shape):
    x = rand(shape, scale=4.0)
    got = ops.softmax(x)
    assert_close(got, ref.softmax_ref(x), rtol=1e-4)
    assert_close(np.asarray(got).sum(-1), np.ones(shape[0]), rtol=1e-4)


def test_softmax_extreme_values():
    x = jnp.array([[1e4, 0.0, -1e4], [-1e4, -1e4, -1e4]], jnp.float32)
    got = np.asarray(ops.softmax(x))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got.sum(-1), [1.0, 1.0], rtol=1e-5)


@pytest.mark.parametrize("act", ["relu", "silu", "gelu", "tanh"])
def test_elementwise(act):
    x = rand((33, 257), scale=2.0)   # deliberately unaligned
    fns = {"relu": jax.nn.relu, "silu": jax.nn.silu,
           "gelu": jax.nn.gelu, "tanh": jnp.tanh}
    assert_close(ops.elementwise(x, act), fns[act](x), rtol=1e-4)


# ---------------------------------------------------------------------------
# int8 matmul (roadmap item 2: reduced precision)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (64, 512, 256)])
def test_int8_matmul(m, k, n):
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    aq = jax.random.randint(k1, (m, k), -127, 128, jnp.int8)
    bq = jax.random.randint(k2, (k, n), -127, 128, jnp.int8)
    asc = jnp.abs(jax.random.normal(k3, (m,))) + 0.01
    bsc = jnp.abs(jax.random.normal(k4, (n,))) + 0.01
    assert_close(ops.int8_matmul(aq, bq, asc, bsc),
                 ref.int8_matmul_ref(aq, bq, asc, bsc), rtol=1e-5)


def test_int8_matmul_accumulates_in_int32():
    # 512 * 127 * 127 overflows int16 but not int32
    aq = jnp.full((8, 512), 127, jnp.int8)
    bq = jnp.full((512, 8), 127, jnp.int8)
    sc = jnp.ones((8,))
    got = np.asarray(ops.int8_matmul(aq, bq, sc, sc))
    assert np.all(got == 512 * 127 * 127)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("s,h,kv,d", [(256, 8, 8, 64),   # MHA
                                      (256, 8, 4, 64),   # GQA
                                      (512, 4, 1, 64),   # MQA
                                      (128, 2, 2, 128)])
def test_flash_attention_head_layouts(s, h, kv, d):
    ks = jax.random.split(KEY, 3)
    q = rand((2, s, h, d), key=ks[0])
    k = rand((2, s, kv, d), key=ks[1])
    v = rand((2, s, kv, d), key=ks[2])
    assert_close(ops.flash_attention(q, k, v),
                 ref.flash_attention_ref(q, k, v), rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("window", [32, 64, 128])
def test_flash_attention_sliding_window(window):
    ks = jax.random.split(KEY, 3)
    q = rand((1, 256, 4, 32), key=ks[0])
    k = rand((1, 256, 2, 32), key=ks[1])
    v = rand((1, 256, 2, 32), key=ks[2])
    assert_close(ops.flash_attention(q, k, v, window=window),
                 ref.flash_attention_ref(q, k, v, window=window),
                 rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    ks = jax.random.split(KEY, 3)
    q = rand((1, 128, 4, 64), dtype, key=ks[0])
    k = rand((1, 128, 4, 64), dtype, key=ks[1])
    v = rand((1, 128, 4, 64), dtype, key=ks[2])
    rtol = 3e-2 if dtype == jnp.bfloat16 else 2e-3
    assert_close(ops.flash_attention(q, k, v),
                 ref.flash_attention_ref(q, k, v), rtol=rtol, atol=3e-2)


def test_flash_attention_causality():
    """Perturbing a future token must not change earlier outputs."""
    ks = jax.random.split(KEY, 3)
    q = rand((1, 128, 2, 32), key=ks[0])
    k = rand((1, 128, 2, 32), key=ks[1])
    v = rand((1, 128, 2, 32), key=ks[2])
    base = np.asarray(ops.flash_attention(q, k, v))
    k2 = k.at[:, -1].add(10.0)
    v2 = v.at[:, -1].add(10.0)
    pert = np.asarray(ops.flash_attention(q, k2, v2))
    np.testing.assert_allclose(base[:, :-1], pert[:, :-1], rtol=1e-5)
    assert np.abs(base[:, -1] - pert[:, -1]).max() > 1e-3


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("s,h,kv,d", [(512, 8, 4, 64), (1024, 4, 1, 32),
                                      (256, 16, 16, 64)])
def test_decode_attention(s, h, kv, d):
    ks = jax.random.split(KEY, 3)
    q = rand((2, h, d), key=ks[0])
    k = rand((2, kv, s, d), key=ks[1])
    v = rand((2, kv, s, d), key=ks[2])
    for valid in (1, s // 3, s):
        assert_close(ops.decode_attention(q, k, v, jnp.int32(valid)),
                     ref.decode_attention_ref(q, k, v, valid),
                     rtol=2e-3, atol=2e-3)


def test_decode_attention_masks_invalid_slots():
    """Garbage beyond valid_len must not affect the output."""
    ks = jax.random.split(KEY, 3)
    q = rand((1, 4, 32), key=ks[0])
    k = rand((1, 4, 128, 32), key=ks[1])
    v = rand((1, 4, 128, 32), key=ks[2])
    out1 = np.asarray(ops.decode_attention(q, k, v, jnp.int32(64)))
    k2 = k.at[:, :, 64:].set(99.0)
    v2 = v.at[:, :, 64:].set(-99.0)
    out2 = np.asarray(ops.decode_attention(q, k2, v2, jnp.int32(64)))
    np.testing.assert_allclose(out1, out2, rtol=1e-5)


def _valid(kind, s):
    """Per-lane (B=4,) valid lengths — the continuous-batching shape —
    or one scalar length broadcast to every lane."""
    if kind == "ragged":
        return jnp.array([1, s // 3, s // 2 + 1, s], jnp.int32)
    return jnp.int32(s // 2 + 1)


@pytest.mark.parametrize("valid_kind", ["scalar", "ragged"])
@pytest.mark.parametrize("s,h,kv,d,block", [(256, 8, 4, 64, 64),
                                            (128, 4, 1, 32, 128),
                                            (192, 16, 16, 32, 64)])
def test_decode_attention_ragged(valid_kind, s, h, kv, d, block):
    """Per-lane (B,) or scalar valid_len across GQA group counts and
    block counts that force the @pl.when early-exit path (valid not a
    block multiple)."""
    b = 4
    ks = jax.random.split(KEY, 3)
    q = rand((b, h, d), key=ks[0])
    k = rand((b, kv, s, d), key=ks[1])
    v = rand((b, kv, s, d), key=ks[2])
    valid = _valid(valid_kind, s)
    assert_close(
        ops.decode_attention(q, k, v, valid, block_s=block),
        ref.decode_attention_ref(q, k, v, valid),
        rtol=2e-3, atol=2e-3)


def test_decode_attention_ragged_matches_per_lane_scalar():
    """Lane i of one ragged launch == a solo scalar-valid launch for
    lane i (the batched path must not couple lanes)."""
    b, s, h, kv, d = 3, 128, 8, 4, 32
    ks = jax.random.split(KEY, 3)
    q = rand((b, h, d), key=ks[0])
    k = rand((b, kv, s, d), key=ks[1])
    v = rand((b, kv, s, d), key=ks[2])
    valid = jnp.array([17, 64, 128], jnp.int32)
    ragged = np.asarray(ops.decode_attention(q, k, v, valid, block_s=32))
    for i in range(b):
        solo = np.asarray(ops.decode_attention(
            q[i:i + 1], k[i:i + 1], v[i:i + 1], jnp.int32(int(valid[i])),
            block_s=32))
        np.testing.assert_allclose(ragged[i:i + 1], solo, rtol=1e-5,
                                   atol=1e-5)


def test_decode_attention_ragged_masks_per_lane():
    """Ring-cache semantics: slots past EACH lane's own valid prefix hold
    stale data that must not leak into that lane's output."""
    b, s, h, kv, d = 3, 128, 4, 2, 32
    ks = jax.random.split(KEY, 3)
    q = rand((b, h, d), key=ks[0])
    k = rand((b, kv, s, d), key=ks[1])
    v = rand((b, kv, s, d), key=ks[2])
    valid = jnp.array([32, 64, 128], jnp.int32)
    out1 = np.asarray(ops.decode_attention(q, k, v, valid, block_s=32))
    k2 = k.at[0, :, 32:].set(99.0).at[1, :, 64:].set(-99.0)
    v2 = v.at[0, :, 32:].set(-99.0).at[1, :, 64:].set(99.0)
    out2 = np.asarray(ops.decode_attention(q, k2, v2, valid, block_s=32))
    np.testing.assert_allclose(out1, out2, rtol=1e-5)


# ---------------------------------------------------------------------------
# decode attention, int8 KV (in-kernel dequant)
# ---------------------------------------------------------------------------


def _quantize_cache(k, v):
    from repro.core.quantize import quantize_into
    kq, ks = quantize_into(k, axis=-1)
    vq, vs = quantize_into(v, axis=-1)
    return kq, vq, ks, vs


@pytest.mark.parametrize("valid_kind", ["scalar", "ragged"])
@pytest.mark.parametrize("s,h,kv,d,block", [(256, 8, 4, 64, 64),
                                            (128, 4, 1, 32, 128),
                                            (192, 16, 16, 32, 64)])
def test_decode_attention_q8_matches_oracle(valid_kind, s, h, kv, d, block):
    """The pallas_q8 kernel must match the ragged q8 jnp oracle exactly
    (same int8 payloads, same scales, fp32 math in both) — including
    ragged valid lengths that force the block-skip early exit to compose
    with the in-kernel dequant."""
    b = 4
    ks = jax.random.split(KEY, 3)
    q = rand((b, h, d), key=ks[0])
    k = rand((b, kv, s, d), key=ks[1])
    v = rand((b, kv, s, d), key=ks[2])
    kq, vq, kscale, vscale = _quantize_cache(k, v)
    valid = _valid(valid_kind, s)
    got = ops.decode_attention_q8(q, kq, vq, kscale, vscale, valid,
                                  block_s=block)
    want = ref.decode_attention_q8_ref(q, kq, vq, kscale, vscale, valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_decode_attention_q8_close_to_fp():
    """In-kernel dequant attention over a quantized cache stays within
    int8 round-trip error of full-precision attention."""
    b, s, h, kv, d = 2, 128, 8, 4, 64
    ks = jax.random.split(KEY, 3)
    q = rand((b, h, d), key=ks[0])
    k = rand((b, kv, s, d), key=ks[1])
    v = rand((b, kv, s, d), key=ks[2])
    kq, vq, kscale, vscale = _quantize_cache(k, v)
    valid = jnp.array([64, 128], jnp.int32)
    got = np.asarray(ops.decode_attention_q8(q, kq, vq, kscale, vscale,
                                             valid))
    want = np.asarray(ref.decode_attention_ref(q, k, v, valid))
    assert np.abs(got - want).max() < 0.05


def test_decode_attention_q8_masks_per_lane():
    """Stale int8 payloads AND stale scales past each lane's valid
    prefix must not leak into that lane's output."""
    b, s, h, kv, d = 3, 128, 4, 2, 32
    ks = jax.random.split(KEY, 3)
    q = rand((b, h, d), key=ks[0])
    k = rand((b, kv, s, d), key=ks[1])
    v = rand((b, kv, s, d), key=ks[2])
    kq, vq, kscale, vscale = _quantize_cache(k, v)
    valid = jnp.array([32, 64, 128], jnp.int32)
    out1 = np.asarray(ops.decode_attention_q8(q, kq, vq, kscale, vscale,
                                              valid, block_s=32))
    kq2 = kq.at[0, :, 32:].set(127).at[1, :, 64:].set(-127)
    vq2 = vq.at[0, :, 32:].set(-127).at[1, :, 64:].set(127)
    ks2 = kscale.at[0, :, 32:].set(99.0).at[1, :, 64:].set(99.0)
    vs2 = vscale.at[0, :, 32:].set(99.0).at[1, :, 64:].set(99.0)
    out2 = np.asarray(ops.decode_attention_q8(q, kq2, vq2, ks2, vs2,
                                              valid, block_s=32))
    np.testing.assert_allclose(out1, out2, rtol=1e-5)


# ---------------------------------------------------------------------------
# rwkv6 chunked scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("t,chunk", [(32, 16), (48, 16), (33, 16), (64, 32)])
def test_rwkv6_chunked_vs_recurrent(t, chunk):
    ks = jax.random.split(KEY, 5)
    b, h, n = 2, 4, 16
    r = rand((b, t, h, n), key=ks[0])
    k = rand((b, t, h, n), key=ks[1])
    v = rand((b, t, h, n), key=ks[2])
    w = jax.nn.sigmoid(rand((b, t, h, n), key=ks[3]))
    u = rand((h, n), key=ks[4])
    out_c, s_c = ops.rwkv6_chunked(r, k, v, w, u, chunk=chunk)
    out_r, s_r = ref.rwkv6_ref(r, k, v, w, u)
    assert_close(out_c, out_r, rtol=1e-3, atol=1e-4)
    assert_close(s_c, s_r, rtol=1e-3, atol=1e-4)


def test_rwkv6_state_carry_composes():
    """Running [0:T] in one go == running [0:T/2] then [T/2:T] with the
    carried state."""
    ks = jax.random.split(KEY, 5)
    b, t, h, n = 1, 32, 2, 8
    r = rand((b, t, h, n), key=ks[0])
    k = rand((b, t, h, n), key=ks[1])
    v = rand((b, t, h, n), key=ks[2])
    w = jax.nn.sigmoid(rand((b, t, h, n), key=ks[3]))
    u = rand((h, n), key=ks[4])
    full, s_full = ref.rwkv6_ref(r, k, v, w, u)
    h1, s1 = ref.rwkv6_ref(r[:, :16], k[:, :16], v[:, :16], w[:, :16], u)
    h2, s2 = ref.rwkv6_ref(r[:, 16:], k[:, 16:], v[:, 16:], w[:, 16:], u,
                           s0=s1)
    assert_close(np.concatenate([h1, h2], 1), full, rtol=1e-4)
    assert_close(s2, s_full, rtol=1e-4)


# ---------------------------------------------------------------------------
# flash attention FUSED BACKWARD (custom VJP) — the §Perf "real fix"
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("h,kv,window", [(4, 4, 0), (4, 2, 0), (4, 1, 0),
                                         (4, 2, 32)])
def test_flash_attention_trainable_grads(h, kv, window):
    """Fused Pallas backward == jax.grad of the naive oracle."""
    from repro.kernels.flash_attention_bwd import flash_attention_trainable
    from repro.models.common import attention_full
    ks = jax.random.split(KEY, 3)
    B, S, D = 1, 128, 32
    q = rand((B, S, h, D), key=ks[0])
    k = rand((B, S, kv, D), key=ks[1])
    v = rand((B, S, kv, D), key=ks[2])

    def loss_flash(q, k, v):
        o = flash_attention_trainable(q, k, v, True, window, 64, 64, True)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        o = attention_full(q, k, v, causal=True, window=window)
        return jnp.sum(jnp.sin(o))

    o1 = flash_attention_trainable(q, k, v, True, window, 64, 64, True)
    assert_close(o1, attention_full(q, k, v, causal=True, window=window),
                 rtol=1e-4, atol=1e-4)
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_flash, g_ref):
        assert_close(a, b, rtol=1e-3, atol=1e-4,
                     err_msg=f"d{name} mismatch (h={h} kv={kv} w={window})")


def test_flash_attention_trainable_block_shapes():
    """Gradients are block-size invariant."""
    from repro.kernels.flash_attention_bwd import flash_attention_trainable
    ks = jax.random.split(KEY, 3)
    q = rand((1, 128, 2, 32), key=ks[0])
    k = rand((1, 128, 2, 32), key=ks[1])
    v = rand((1, 128, 2, 32), key=ks[2])

    def loss(bq, bk):
        def f(q, k, v):
            return jnp.sum(flash_attention_trainable(
                q, k, v, True, 0, bq, bk, True) ** 2)
        return jax.grad(f)(q, k, v)

    g64 = loss(64, 64)
    g32 = loss(32, 128)
    assert_close(g64, g32, rtol=1e-4)
