"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret=True)."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.mamba_scan.ops import mamba_scan
from repro.kernels.mamba_scan.ref import mamba_scan_ref
from repro.core import bitmask
from repro.kernels.masked_sample.ops import masked_argmax
from repro.kernels.masked_sample.ref import masked_argmax_ref

RNG = np.random.default_rng(42)


# odd V (tail tiles padded, not collapsed to one whole-V VMEM tile) and
# packed uint32 masks ride the same sweep as the original shapes
@pytest.mark.parametrize("b,v,bv", [(1, 512, 128), (4, 8192, 2048),
                                    (2, 1000, 2048), (3, 4096, 512),
                                    (2, 4100, 2048), (1, 333, 128),
                                    (2, 262144, 2048)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
@pytest.mark.parametrize("packed", [False, True])
def test_masked_argmax(b, v, bv, dtype, packed):
    logits = jnp.asarray(RNG.normal(size=(b, v)), dtype=dtype)
    mask_np = RNG.random((b, v)) < 0.02
    mask_np[:, v // 3] = True
    mask = jnp.asarray(bitmask.pack_bool(mask_np)) if packed \
        else jnp.asarray(mask_np.astype(np.int8))
    i1, v1 = masked_argmax(logits, mask, block_v=bv)
    i2, v2 = masked_argmax_ref(logits, jnp.asarray(mask_np.astype(np.int8)))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-6)


@pytest.mark.parametrize("packed", [False, True])
def test_masked_argmax_respects_mask(packed):
    logits = jnp.asarray(RNG.normal(size=(2, 256)).astype(np.float32)) + 10
    mask_np = np.zeros((2, 256), np.int8)
    mask_np[:, 5] = 1
    mask = jnp.asarray(bitmask.pack_bool(mask_np)) if packed \
        else jnp.asarray(mask_np)
    i, _ = masked_argmax(logits, mask, block_v=64)
    assert list(np.asarray(i)) == [5, 5]


@pytest.mark.parametrize("b,g,q,d,t,bt", [
    (2, 2, 4, 64, 1024, 256), (1, 8, 1, 128, 2048, 512),
    (2, 1, 8, 32, 100, 512), (1, 2, 2, 128, 4096, 1024)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_decode_attention(b, g, q, d, t, bt, dtype):
    qq = jnp.asarray(RNG.normal(size=(b, g, q, d)), dtype=dtype)
    k = jnp.asarray(RNG.normal(size=(b, t, g, d)), dtype=dtype)
    v = jnp.asarray(RNG.normal(size=(b, t, g, d)), dtype=dtype)
    ln = jnp.int32(max(1, t - 13))
    o1 = decode_attention(qq, k, v, ln, block_t=bt)
    o2 = decode_attention_ref(qq, k, v, ln)
    atol = 3e-5 if dtype == np.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), atol=atol,
                               rtol=1e-3)


def test_decode_attention_length_masking():
    b, g, q, d, t = 1, 1, 1, 16, 64
    qq = jnp.ones((b, g, q, d), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, t, g, d)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(b, t, g, d)).astype(np.float32))
    o_5 = decode_attention(qq, k, v, jnp.int32(5), block_t=16)
    # zeroing the cache beyond length must not change the output
    k2 = k.at[:, 5:].set(123.0)
    v2 = v.at[:, 5:].set(-55.0)
    o_5b = decode_attention(qq, k2, v2, jnp.int32(5), block_t=16)
    np.testing.assert_allclose(np.asarray(o_5), np.asarray(o_5b), atol=1e-6)


@pytest.mark.parametrize("lens", [
    [100, 7], [0, 1], [13, 256], [256, 0], [5, 64]])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_decode_attention_ragged(lens, dtype):
    """Per-row (B,) lengths: rows shorter than one BLOCK_T, full rows and
    length-0 empty slots must all match the oracle."""
    b, g, qh, d, t, bt = 2, 2, 4, 32, 256, 64
    qq = jnp.asarray(RNG.normal(size=(b, g, qh, d)), dtype=dtype)
    k = jnp.asarray(RNG.normal(size=(b, t, g, d)), dtype=dtype)
    v = jnp.asarray(RNG.normal(size=(b, t, g, d)), dtype=dtype)
    ln = jnp.asarray(lens, jnp.int32)
    o1 = decode_attention(qq, k, v, ln, block_t=bt)
    o2 = decode_attention_ref(qq, k, v, ln)
    atol = 3e-5 if dtype == np.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), atol=atol,
                               rtol=1e-3)


def test_decode_attention_empty_row_is_zero():
    """A length-0 row (empty serving slot) yields zeros, not an average
    over garbage cache entries."""
    b, g, qh, d, t = 2, 1, 2, 16, 64
    qq = jnp.asarray(RNG.normal(size=(b, g, qh, d)).astype(np.float32))
    k = jnp.full((b, t, g, d), 3.0, jnp.float32)
    v = jnp.full((b, t, g, d), 7.0, jnp.float32)
    o = decode_attention(qq, k, v, jnp.asarray([0, 4], jnp.int32),
                         block_t=16)
    np.testing.assert_allclose(np.asarray(o[0]), 0.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(o[1]), 7.0, rtol=1e-5)


@pytest.mark.parametrize("s_win,lens", [
    (2, [60, 250]), (4, [0, 17]), (3, [100, 100])])
def test_decode_attention_verify_window(s_win, lens):
    """Q>1 speculative verify windows: window position s of row b attends
    keys t < lengths[b] + s (causal offsets), matching the oracle."""
    b, g, qh, d, t, bt = 2, 2, 2, 32, 256, 64
    qq = jnp.asarray(RNG.normal(size=(b, s_win, g, qh, d)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(b, t, g, d)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(b, t, g, d)).astype(np.float32))
    ln = jnp.asarray(lens, jnp.int32)
    o1 = decode_attention(qq, k, v, ln, block_t=bt)
    o2 = decode_attention_ref(qq, k, v, ln)
    assert o1.shape == (b, s_win, g, qh, d)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=3e-5,
                               rtol=1e-3)
    # window position 0 must agree with a plain S=1 call at the same length
    o_pos0 = decode_attention(qq[:, :1], k, v, ln, block_t=bt)
    np.testing.assert_allclose(np.asarray(o1[:, :1]), np.asarray(o_pos0),
                               atol=3e-5, rtol=1e-3)


def test_decode_attention_mla_layout():
    """Absorbed-MLA shape: one KV group, split latent+rope score
    (q.k^T + q2.k2^T) against Dv = r latent values, explicit softmax
    scale — and the split form must equal the concatenated form."""
    b, h, r, dr, t = 2, 4, 16, 8, 128
    scale = 0.17
    q1 = jnp.asarray(RNG.normal(size=(b, 1, 1, h, r)).astype(np.float32))
    q2 = jnp.asarray(RNG.normal(size=(b, 1, 1, h, dr)).astype(np.float32))
    k1 = jnp.asarray(RNG.normal(size=(b, t, 1, r)).astype(np.float32))
    k2 = jnp.asarray(RNG.normal(size=(b, t, 1, dr)).astype(np.float32))
    v = k1                                 # MLA: values ARE the latents
    ln = jnp.asarray([100, 3], jnp.int32)
    o1 = decode_attention(q1, k1, v, ln, block_t=32, scale=scale,
                          q2=q2, k2=k2)
    o2 = decode_attention_ref(q1, k1, v, ln, scale=scale, q2=q2, k2=k2)
    assert o1.shape == (b, 1, 1, h, r)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=3e-5,
                               rtol=1e-3)
    # split == concat
    o3 = decode_attention(jnp.concatenate([q1, q2], -1),
                          jnp.concatenate([k1, k2], -1), v, ln,
                          block_t=32, scale=scale)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o3), atol=3e-5,
                               rtol=1e-3)


@pytest.mark.parametrize("b,s,d,n,bd,bs", [
    (2, 64, 32, 8, 16, 16), (1, 128, 512, 16, 512, 128),
    (2, 100, 48, 8, 48, 100), (1, 256, 64, 16, 32, 64)])
def test_mamba_scan(b, s, d, n, bd, bs):
    dt = jnp.asarray(np.abs(RNG.normal(size=(b, s, d))).astype(np.float32)
                     * 0.1)
    x = jnp.asarray(RNG.normal(size=(b, s, d)).astype(np.float32))
    bm = jnp.asarray(RNG.normal(size=(b, s, n)).astype(np.float32))
    cm = jnp.asarray(RNG.normal(size=(b, s, n)).astype(np.float32))
    a = jnp.asarray(-np.abs(RNG.normal(size=(d, n))).astype(np.float32))
    h0 = jnp.asarray(RNG.normal(size=(b, d, n)).astype(np.float32))
    y1, h1 = mamba_scan(dt, x, bm, cm, a, h0, block_d=bd, block_s=bs)
    y2, h2 = mamba_scan_ref(dt, x, bm, cm, a, h0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=1e-4,
                               rtol=1e-4)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 3), st.integers(1, 4), st.integers(2, 32))
def test_mamba_scan_property(b, chunks, d):
    """State continuity: scanning in one go == chunked with carried h."""
    s = chunks * 16
    n = 4
    dt = jnp.asarray(np.abs(RNG.normal(size=(b, s, d))).astype(np.float32)
                     * 0.1)
    x = jnp.asarray(RNG.normal(size=(b, s, d)).astype(np.float32))
    bm = jnp.asarray(RNG.normal(size=(b, s, n)).astype(np.float32))
    cm = jnp.asarray(RNG.normal(size=(b, s, n)).astype(np.float32))
    a = jnp.asarray(-np.abs(RNG.normal(size=(d, n))).astype(np.float32))
    h = jnp.zeros((b, d, n), jnp.float32)
    y_full, h_full = mamba_scan_ref(dt, x, bm, cm, a, h)
    ys = []
    for c in range(chunks):
        sl = slice(c * 16, (c + 1) * 16)
        y, h = mamba_scan(dt[:, sl], x[:, sl], bm[:, sl], cm[:, sl], a, h,
                          block_d=d, block_s=16)
        ys.append(np.asarray(y))
    np.testing.assert_allclose(np.concatenate(ys, 1), np.asarray(y_full),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_full), atol=1e-4,
                               rtol=1e-4)
