"""Per-kernel validation: Pallas (interpret mode) vs the pure-jnp oracle,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

TOL = dict(rtol=2e-2, atol=2e-2)
TOL_TIGHT = dict(rtol=1e-4, atol=1e-5)


def ok(a, b, tol=TOL):
    np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32), **tol
    )


# ------------------------------ flash attention --------------------------- #
@pytest.mark.parametrize("b,s,h,hkv,d", [
    (1, 128, 4, 4, 32),    # MHA
    (2, 256, 4, 2, 64),    # GQA
    (1, 256, 8, 1, 64),    # MQA
    (2, 200, 4, 2, 32),    # non-block-multiple seq (pad path)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_causal(rng, b, s, h, hkv, d, dtype):
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), dtype)
    tol = TOL if dtype == jnp.float32 else dict(rtol=8e-2, atol=8e-2)
    ok(ops.flash_attention(q, k, v, impl="pallas"),
       ops.flash_attention(q, k, v, impl="xla"), tol)


@pytest.mark.parametrize("window", [32, 100, 256])
def test_flash_attention_sliding_window(rng, window):
    b, s, h, hkv, d = 2, 256, 4, 2, 32
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    ok(ops.flash_attention(q, k, v, window=window, impl="pallas"),
       ops.flash_attention(q, k, v, window=window, impl="xla"))


def test_xla_chunked_matches_dense(rng):
    """The memory-bounded chunked XLA path is exact vs dense."""
    b, s, h, hkv, d = 1, 1024, 2, 1, 32
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    dense = ref.mha_attention(q, k, v, chunk_q=0)
    chunked = ref.mha_attention(q, k, v, chunk_q=256)
    unrolled = ref.mha_attention(q, k, v, chunk_q=256, unroll=True)
    ok(chunked, dense, TOL_TIGHT)
    ok(unrolled, dense, TOL_TIGHT)


def test_xla_chunked_swa_banded(rng):
    b, s, h, hkv, d = 1, 1024, 2, 1, 32
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    dense = ref.mha_attention(q, k, v, window=128, chunk_q=0)
    banded = ref.mha_attention(q, k, v, window=128, chunk_q=256)
    ok(banded, dense, TOL_TIGHT)


# ------------------------------ decode attention -------------------------- #
@pytest.mark.parametrize("b,s,h,hkv,d", [
    (2, 512, 4, 2, 64), (1, 256, 8, 8, 32), (3, 512, 8, 1, 64),
])
def test_decode_attention(rng, b, s, h, hkv, d):
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    lens = jnp.asarray(rng.integers(1, s + 1, (b,)), jnp.int32)
    ok(ops.decode_attention(q, kc, vc, lens, impl="pallas"),
       ops.decode_attention(q, kc, vc, lens, impl="xla"))


def test_decode_attention_matches_full(rng):
    """Decode vs full attention at the last position."""
    b, s, h, hkv, d = 2, 128, 4, 2, 32
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    full = ref.mha_attention(q, k, v, causal=True)[:, -1]
    dec = ref.decode_attention(q[:, -1], k, v, jnp.full((b,), s, jnp.int32))
    ok(dec, full, TOL_TIGHT)


# ------------------------------ RG-LRU ------------------------------------ #
@pytest.mark.parametrize("b,s,w", [(1, 64, 64), (2, 128, 128), (2, 96, 256)])
def test_rglru(rng, b, s, w):
    x = jnp.asarray(rng.standard_normal((b, s, w)), jnp.float32)
    r = jnp.asarray(rng.standard_normal((b, s, w)), jnp.float32)
    i = jnp.asarray(rng.standard_normal((b, s, w)), jnp.float32)
    a = jnp.asarray(rng.standard_normal((w,)), jnp.float32)
    h0 = jnp.asarray(rng.standard_normal((b, w)), jnp.float32)
    o1, h1 = ops.rglru(x, r, i, a, h0, impl="xla")
    # block_w is a multiple of 128 or the full width, as the TPU compiler
    # requires; w=256 still runs two width blocks
    o2, h2 = ops.rglru(x, r, i, a, h0, impl="pallas", block_s=32, block_w=128)
    ok(o2, o1)
    ok(h2, h1)


def test_rglru_state_chaining(rng):
    """Running two halves with state == running the whole sequence."""
    b, s, w = 2, 64, 32
    x = jnp.asarray(rng.standard_normal((b, s, w)), jnp.float32)
    r = jnp.asarray(rng.standard_normal((b, s, w)), jnp.float32)
    i = jnp.asarray(rng.standard_normal((b, s, w)), jnp.float32)
    a = jnp.asarray(rng.standard_normal((w,)), jnp.float32)
    o_full, h_full = ref.rglru(x, r, i, a)
    o1, h1 = ref.rglru(x[:, :32], r[:, :32], i[:, :32], a)
    o2, h2 = ref.rglru(x[:, 32:], r[:, 32:], i[:, 32:], a, h1)
    ok(jnp.concatenate([o1, o2], 1), o_full, TOL_TIGHT)
    ok(h2, h_full, TOL_TIGHT)


# ------------------------------ SSD (mamba2) ------------------------------- #
@pytest.mark.parametrize("b,s,h,p,g,n,chunk", [
    (1, 64, 2, 16, 1, 16, 16),
    (2, 128, 4, 32, 2, 16, 32),
    (1, 128, 4, 64, 1, 32, 64),
])
def test_ssd(rng, b, s, h, p, g, n, chunk):
    x = jnp.asarray(rng.standard_normal((b, s, h, p)) * 0.5, jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (b, s, h)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, (h,)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((b, s, g, n)) * 0.3, jnp.float32)
    C = jnp.asarray(rng.standard_normal((b, s, g, n)) * 0.3, jnp.float32)
    h0 = jnp.zeros((b, h, p, n), jnp.float32)
    y1, hl1 = ops.ssd(x, dt, A, B, C, h0, chunk=chunk, impl="xla")
    y2, hl2 = ops.ssd(x, dt, A, B, C, h0, chunk=chunk, impl="pallas")
    ok(y2, y1, dict(rtol=3e-2, atol=3e-2))
    ok(hl2, hl1, dict(rtol=3e-2, atol=3e-2))


def test_ssd_chunk_invariance(rng):
    """Chunk size is an implementation detail: results must not change."""
    b, s, h, p, g, n = 1, 128, 2, 16, 1, 16
    x = jnp.asarray(rng.standard_normal((b, s, h, p)) * 0.5, jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (b, s, h)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, (h,)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((b, s, g, n)) * 0.3, jnp.float32)
    C = jnp.asarray(rng.standard_normal((b, s, g, n)) * 0.3, jnp.float32)
    y1, h1 = ref.ssd(x, dt, A, B, C, chunk=32)
    y2, h2 = ref.ssd(x, dt, A, B, C, chunk=64)
    ok(y1, y2, TOL)
    ok(h1, h2, TOL)


def test_ssd_decode_consistency(rng):
    """Recurrent decode step == last position of the chunked scan."""
    b, s, h, p, g, n = 1, 65, 2, 16, 1, 16
    x = jnp.asarray(rng.standard_normal((b, s, h, p)) * 0.5, jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (b, s, h)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, (h,)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((b, s, g, n)) * 0.3, jnp.float32)
    C = jnp.asarray(rng.standard_normal((b, s, g, n)) * 0.3, jnp.float32)
    y_pre, h_pre = ref.ssd(x[:, :64], dt[:, :64], A, B[:, :64], C[:, :64], chunk=32)
    y_step, h_step = ref.ssd_decode_step(
        x[:, 64], dt[:, 64], A, B[:, 64], C[:, 64], h_pre
    )
    # full scan over 65 requires chunk divisibility; compare via 1-chunk run
    y_full, h_full = ref.ssd(
        x[:, 64:65], dt[:, 64:65], A, B[:, 64:65], C[:, 64:65], h_pre, chunk=1
    )
    ok(y_step, y_full[:, 0], TOL)
    ok(h_step, h_full, TOL)


# ------------------------------ HSV color --------------------------------- #
@pytest.mark.parametrize("b,h,w", [(2, 32, 16), (4, 64, 64), (1, 96, 48)])
def test_hsv_color(rng, b, h, w):
    crops = jnp.asarray(rng.uniform(0, 255, (b, h, w, 3)), jnp.float32)
    h1, l1 = ops.hsv_color_classify(crops, impl="xla")
    h2, l2 = ops.hsv_color_classify(crops, impl="pallas", block_rows=16)
    ok(h2, h1, TOL_TIGHT)
    assert (np.asarray(l1) == np.asarray(l2)).all()


def test_hsv_known_colors():
    """Solid-color crops classify to their color (paper's HSV table)."""
    solid = {
        "black": (5, 5, 5), "white": (250, 250, 250), "red": (220, 30, 30),
        "green": (40, 200, 40), "blue": (40, 60, 220), "yellow": (230, 220, 30),
    }
    crops = np.zeros((len(solid), 16, 16, 3), np.float32)
    for i, rgb in enumerate(solid.values()):
        crops[i] = np.asarray(rgb, np.float32)
    _, labels = ops.hsv_color_classify(jnp.asarray(crops), impl="xla")
    got = [ref.COLOR_NAMES[int(i)] for i in np.asarray(labels)]
    assert got == list(solid), got


# ------------------------------ MoE router --------------------------------- #
@pytest.mark.parametrize("t,e,k", [(64, 8, 2), (128, 16, 2), (32, 4, 1)])
def test_moe_router(rng, t, e, k):
    logits = jnp.asarray(rng.standard_normal((t, e)), jnp.float32)
    w1, i1 = ops.moe_topk_router(logits, k, impl="xla")
    w2, i2 = ops.moe_topk_router(logits, k, impl="pallas", block_t=16)
    ok(w2, w1, TOL_TIGHT)
    assert (np.asarray(i1) == np.asarray(i2)).all()
    # weights renormalized
    np.testing.assert_allclose(np.asarray(w1.sum(-1)), 1.0, rtol=1e-5)
