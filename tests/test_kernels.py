"""Per-kernel allclose vs the pure-jnp oracle: shape/dtype sweeps in
interpret mode (the kernel body executes in Python on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.models import attention

RNG = np.random.default_rng(0)


def _rand(shape, dtype=jnp.float32, scale=1.0, seed=None):
    x = RNG.standard_normal(shape).astype(np.float32) * scale
    return jnp.asarray(x, dtype)


# ---------------------------------------------------------------------------
# image complexity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("h,w", [(16, 16), (33, 65), (64, 128), (128, 96)])
def test_image_stats_matches_ref(h, w):
    imgs = jnp.asarray(RNG.uniform(0, 255, (2, h, w)), jnp.float32)
    got = ops.image_stats(imgs, interpret=True)
    want = ref.image_stats_batch_ref(imgs)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=2e-5, atol=1e-3)


def test_image_stats_histogram_counts_pixels():
    imgs = jnp.asarray(RNG.uniform(0, 255, (3, 40, 56)), jnp.float32)
    got = ops.image_stats(imgs, interpret=True)
    np.testing.assert_allclose(got["hist"].sum(axis=-1), 40 * 56)


def test_image_complexity_flat_vs_textured():
    flat = jnp.full((1, 64, 64), 128.0)
    tex = jnp.asarray(RNG.uniform(0, 255, (1, 64, 64)), jnp.float32)
    c_flat = ops.image_complexity(flat, interpret=True)["c_img"][0]
    c_tex = ops.image_complexity(tex, interpret=True)["c_img"][0]
    assert float(c_tex) > float(c_flat)


def test_image_complexity_components_in_unit_interval():
    imgs = jnp.asarray(RNG.uniform(0, 255, (4, 48, 48)), jnp.float32)
    out = ops.image_complexity(imgs, interpret=True)
    for k, v in out.items():
        assert jnp.all(v >= 0.0) and jnp.all(v <= 1.0), k


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("s,h,kh,hd", [
    (128, 4, 4, 32),   # MHA
    (128, 8, 2, 64),   # GQA
    (256, 4, 1, 64),   # MQA
    (128, 4, 4, 80),   # non-128 head dim (padding path)
])
def test_flash_attention_shapes(s, h, kh, hd):
    q = _rand((2, s, h, hd))
    k = _rand((2, s, kh, hd))
    v = _rand((2, s, kh, hd))
    got = ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                              interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    q = _rand((1, 128, 4, 64), dtype)
    k = _rand((1, 128, 2, 64), dtype)
    v = _rand((1, 128, 2, 64), dtype)
    got = ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                              interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_bidirectional_and_window():
    q = _rand((1, 256, 4, 32))
    k = _rand((1, 256, 2, 32))
    v = _rand((1, 256, 2, 32))
    for causal, window in [(False, None), (True, 64)]:
        got = ops.flash_attention(q, k, v, causal=causal, window=window,
                                  block_q=64, block_k=64, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("t,h,kh,hd,fill", [
    (256, 4, 4, 32, 256),   # full cache
    (512, 8, 2, 64, 300),   # partially filled
    (512, 4, 1, 128, 100),  # MQA
])
def test_decode_attention_vs_ref(t, h, kh, hd, fill):
    b = 2
    q = _rand((b, 1, h, hd))
    kc = _rand((b, t, kh, hd))
    vc = _rand((b, t, kh, hd))
    pos_c = np.full((b, t), -1, np.int32)
    pos_c[:, :fill] = np.arange(fill)
    pos_c = jnp.asarray(pos_c)
    pq = jnp.full((b,), fill - 1, jnp.int32)
    got = ops.decode_attention(q, kc, vc, pq, pos_c, block_t=128,
                               interpret=True)
    want = ref.decode_attention_ref(q, kc, vc, pq, pos_c)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("b,h,kh,page,n_pt,width,window,fills", [
    pytest.param(2, 8, 2, 16, 4, 64, None, None,   # GQA, every row in table
                 id="2-8-2-16-4-64-None"),
    pytest.param(3, 4, 1, 16, 4, 50, None, None,   # MQA, ragged last page
                 id="3-4-1-16-4-50-None"),
    pytest.param(2, 4, 2, 32, 3, 96, 40, None,     # sliding window
                 id="2-4-2-32-3-96-40"),
    # ragged rows in one batch: no live entry, one live page, every page,
    # and one ending mid-table (pages past each frontier hold NaN)
    pytest.param(4, 8, 2, 16, 4, 64, None, (0, 9, 64, 33), id="ragged"),
    pytest.param(3, 4, 1, 16, 4, 50, None, (50, 0, 16), id="ragged-tail"),
    pytest.param(4, 4, 2, 32, 3, 96, 40, (0, 20, 96, 50),
                 id="ragged-window"),
])
def test_decode_attention_paged_vs_ref(b, h, kh, page, n_pt, width, window,
                                       fills):
    """Pages are read THROUGH each row's table out of a shared pool, so the
    kernel must equal dense decode over the gathered context. Every row owns
    its pages, and the pages wholly past a row's last live one hold NaN: a
    kernel that read them would turn the row to NaN, so matching the
    reference on the clean pool proves them unread. A row with no live
    entry has no defined output (the engine discards it) beyond being
    finite."""
    hd, n_pool = 64, 1 + b * n_pt
    q = _rand((b, 1, h, hd))
    kp = _rand((n_pool, page, kh, hd))
    vp = _rand((n_pool, page, kh, hd))
    pages = RNG.permutation(np.arange(1, n_pool)).reshape(b, n_pt)
    pages = pages.astype(np.int32)
    fill = (RNG.integers(1, width + 1, size=b) if fills is None
            else np.asarray(fills))
    pos = np.where(np.arange(width)[None] < fill[:, None],
                   np.arange(width)[None], -1).astype(np.int32)
    pq = jnp.asarray(np.maximum(fill - 1, 0), jnp.int32)
    last = np.maximum(-(-fill // page) - 1, 0)
    past = pages[np.arange(n_pt)[None] > last[:, None]]
    got = ops.decode_attention_paged(q, kp.at[past].set(jnp.nan),
                                     vp.at[past].set(jnp.nan),
                                     jnp.asarray(pages), pq,
                                     jnp.asarray(pos), window=window,
                                     interpret=True)
    ctx = lambda pool: pool[pages].reshape(b, n_pt * page, kh, hd)[:, :width]
    want = ref.decode_attention_ref(q, ctx(kp), ctx(vp), pq, jnp.asarray(pos),
                                    window=window)
    live = fill > 0
    np.testing.assert_allclose(got[live], want[live], rtol=2e-5, atol=2e-5)
    xla = attention.decode_attention_paged_xla(
        q, kp, vp, jnp.asarray(pages), pq, jnp.asarray(pos), window=window)
    np.testing.assert_allclose(got[live], xla[live], rtol=2e-5, atol=2e-5)
    assert np.isfinite(np.asarray(got)).all()


def test_decode_attention_ring_buffer_semantics():
    """Slot order must not matter — only absolute positions."""
    b, t, kh, hd = 1, 128, 2, 32
    q = _rand((b, 1, 4, hd))
    kc = _rand((b, t, kh, hd))
    vc = _rand((b, t, kh, hd))
    pos = jnp.asarray(np.arange(t, dtype=np.int32)[None])
    pq = jnp.full((b,), t - 1, jnp.int32)
    base = ops.decode_attention(q, kc, vc, pq, pos, block_t=64, interpret=True)
    roll = 37
    got = ops.decode_attention(q, jnp.roll(kc, roll, 1), jnp.roll(vc, roll, 1),
                               pq, jnp.roll(pos, roll, 1), block_t=64,
                               interpret=True)
    np.testing.assert_allclose(got, base, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("t", [80, 97, 640])
def test_decode_attention_ragged_cache_length(t):
    """t % block_t == 0 is no longer required: a ragged tail block is
    padded with pos=-1 slots (97 is prime) instead of asserting."""
    b, kh, hd = 2, 2, 32
    q = _rand((b, 1, 4, hd))
    kc = _rand((b, t, kh, hd))
    vc = _rand((b, t, kh, hd))
    pos = jnp.asarray(np.arange(t, dtype=np.int32)[None].repeat(b, 0))
    pq = jnp.full((b,), t - 1, jnp.int32)
    got = ops.decode_attention(q, kc, vc, pq, pos, block_t=64, interpret=True)
    want = ref.decode_attention_ref(q, kc, vc, pq, pos)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_kernels_interpret_defaults_resolve():
    """Direct kernel calls with interpret unset resolve via the backend
    (interpret on CPU) instead of the old hardcoded interpret=True."""
    from repro.kernels.decode_attention import decode_attention_pallas
    from repro.kernels.flash_attention import flash_attention_pallas

    b, t, kh, hd = 1, 64, 2, 128  # lane-aligned head dim, no ops.py padding
    q = _rand((b, 1, 4, hd))
    kc = _rand((b, t, kh, hd))
    vc = _rand((b, t, kh, hd))
    pos = jnp.asarray(np.arange(t, dtype=np.int32)[None])
    pq = jnp.full((b,), t - 1, jnp.int32)
    got = decode_attention_pallas(q, kc, vc, pq, pos, block_t=64)
    want = ref.decode_attention_ref(q, kc, vc, pq, pos)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    sq = _rand((b, 64, 4, hd))
    sk = _rand((b, 64, 2, hd))
    sv = _rand((b, 64, 2, hd))
    got = flash_attention_pallas(sq, sk, sv, causal=True, block_q=64,
                                 block_k=64)
    want = ref.flash_attention_ref(sq, sk, sv, causal=True)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_decode_attention_window():
    b, t, hd = 1, 256, 64
    q = _rand((b, 1, 4, hd))
    kc = _rand((b, t, 2, hd))
    vc = _rand((b, t, 2, hd))
    pos = jnp.asarray(np.arange(t, dtype=np.int32)[None])
    pq = jnp.full((b,), t - 1, jnp.int32)
    got = ops.decode_attention(q, kc, vc, pq, pos, window=64, block_t=64,
                               interpret=True)
    want = ref.decode_attention_ref(q, kc, vc, pq, pos, window=64)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
