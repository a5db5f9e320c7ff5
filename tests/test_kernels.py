"""Pallas kernel validation: interpret-mode kernels vs pure-jnp oracles,
swept over shapes/dtypes (+ hypothesis for ragged lengths)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------- paged attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,n_kv,group,D,page,max_pages", [
    (2, 2, 4, 128, 16, 4),
    (4, 1, 8, 128, 16, 8),
    (1, 4, 1, 256, 8, 16),
])
def test_paged_attention_sweep(dtype, B, n_kv, group, D, page, max_pages):
    ks = jax.random.split(KEY, 5)
    num_pages = max_pages * B + 1
    q = jax.random.normal(ks[0], (B, n_kv, group, D)).astype(dtype)
    kp = jax.random.normal(ks[1], (num_pages, page, n_kv, D)).astype(dtype)
    vp = jax.random.normal(ks[2], (num_pages, page, n_kv, D)).astype(dtype)
    bt = jax.random.randint(ks[3], (B, max_pages), 0, num_pages,
                            dtype=jnp.int32)
    lengths = jax.random.randint(ks[4], (B,), 1, max_pages * page + 1,
                                 dtype=jnp.int32)
    out_k = ops.paged_attention(q, kp, vp, bt, lengths, page_size=page,
                                backend="interpret")
    out_r = ref.paged_attention_ref(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32), **_tol(dtype))


@settings(max_examples=10, deadline=None)
@given(lengths=st.lists(st.integers(1, 64), min_size=3, max_size=3))
def test_paged_attention_ragged_lengths(lengths):
    B, n_kv, group, D, page = 3, 2, 2, 128, 16
    max_pages = 4
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, n_kv, group, D))
    kp = jax.random.normal(ks[1], (32, page, n_kv, D))
    vp = jax.random.normal(ks[2], (32, page, n_kv, D))
    bt = jax.random.randint(ks[3], (B, max_pages), 0, 32, dtype=jnp.int32)
    ln = jnp.asarray(lengths, jnp.int32)
    out_k = ops.paged_attention(q, kp, vp, bt, ln, page_size=page,
                                backend="interpret")
    out_r = ref.paged_attention_ref(q, kp, vp, bt, ln)
    np.testing.assert_allclose(out_k, out_r, atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------- flash prefill
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,Hkv,S,D,bq,bk", [
    (1, 2, 1, 256, 128, 64, 64),
    (2, 4, 4, 256, 128, 128, 64),
    (1, 8, 2, 512, 256, 128, 128),
])
def test_flash_prefill_sweep(dtype, B, H, Hkv, S, D, bq, bk):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, S, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, Hkv, S, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, Hkv, S, D)).astype(dtype)
    out_k = ops.flash_prefill(q, k, v, block_q=bq, block_k=bk,
                              backend="interpret")
    out_r = ref.flash_prefill_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32), **_tol(dtype))


@pytest.mark.parametrize("S", [512, 300])
def test_flash_prefill_mup_scale_gqa(S):
    """granite-4.0-h-micro's attention: GQA 32/8 heads of 64, scores times
    the muP attention multiplier 1/64 in place of 1/sqrt(64); a length off
    the block size is padded and the padding dropped."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 32, S, 64))
    k = jax.random.normal(ks[1], (1, 8, S, 64))
    v = jax.random.normal(ks[2], (1, 8, S, 64))
    out_k = ops.flash_prefill(q, k, v, scale=1 / 64, backend="interpret")
    out_r = ref.flash_prefill_ref(q, k, v, scale=1 / 64)
    np.testing.assert_allclose(out_k, out_r, atol=2e-4, rtol=2e-4)
    plain = ref.flash_prefill_ref(q, k, v)
    assert float(jnp.max(jnp.abs(plain - out_r))) > 1e-2


def test_flash_prefill_noncausal():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 128))
    k = jax.random.normal(ks[1], (1, 2, 128, 128))
    v = jax.random.normal(ks[2], (1, 2, 128, 128))
    out_k = ops.flash_prefill(q, k, v, causal=False, block_q=64, block_k=64,
                              backend="interpret")
    out_r = ref.flash_prefill_ref(q, k, v, causal=False)
    np.testing.assert_allclose(out_k, out_r, atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------- ssd scan
@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (1, 128, 2, 64, 32, 32),
    (2, 256, 4, 64, 128, 64),
    (1, 512, 8, 32, 64, 128),
])
def test_ssd_scan_sweep(b, s, h, p, n, chunk):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    B = jax.random.normal(ks[3], (b, s, n))
    C = jax.random.normal(ks[4], (b, s, n))
    y_k, h_k = ops.ssd_scan(x, dt, A, B, C, chunk=chunk, backend="interpret")
    y_r, h_r = ref.ssd_scan_ref(x, dt, A, B, C, chunk=chunk)
    np.testing.assert_allclose(y_k, y_r, atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(h_k, h_r, atol=2e-3, rtol=2e-3)


def test_ssd_chunked_vs_sequential():
    """The chunked 'dual' form must equal the sequential recurrence."""
    ks = jax.random.split(KEY, 5)
    b, s, h, p, n = 2, 192, 3, 32, 16
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    B = jax.random.normal(ks[3], (b, s, n))
    C = jax.random.normal(ks[4], (b, s, n))
    y_c, h_c = ref.ssd_scan_ref(x, dt, A, B, C, chunk=64)
    y_s, h_s = ref.ssd_sequential_ref(x, dt, A, B, C)
    np.testing.assert_allclose(y_c, y_s, atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(h_c, h_s, atol=2e-3, rtol=2e-3)


def test_ssd_nonmultiple_seq_padding():
    """seq % chunk != 0 must work (serving gets arbitrary prompt lengths)."""
    ks = jax.random.split(KEY, 5)
    b, s, h, p, n = 1, 100, 2, 32, 16
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    B = jax.random.normal(ks[3], (b, s, n))
    C = jax.random.normal(ks[4], (b, s, n))
    y_c, h_c = ref.ssd_scan_ref(x, dt, A, B, C, chunk=32)
    y_s, h_s = ref.ssd_sequential_ref(x, dt, A, B, C)
    np.testing.assert_allclose(y_c, y_s, atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(h_c, h_s, atol=2e-3, rtol=2e-3)
