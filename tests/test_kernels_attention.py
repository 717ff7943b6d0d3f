"""flash_attention Pallas kernel vs the pure-jnp oracle (interpret mode),
swept over shapes / dtypes / GQA groups / masking modes, plus the chunked
online-softmax fallback vs the materialized reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention
from repro.kernels import ref


def _qkv(key, B, S, H, KV, hd, dtype):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, hd), dtype)
    k = jax.random.normal(kk, (B, S, KV, hd), dtype)
    v = jax.random.normal(kv, (B, S, KV, hd), dtype)
    return q, k, v


SHAPES = [
    # B, S, H, KV, hd, bq, bk
    (1, 128, 4, 4, 32, 64, 64),
    (2, 256, 4, 2, 16, 64, 128),   # GQA 2:1
    (1, 128, 8, 1, 64, 32, 32),    # MQA
    (2, 64, 2, 2, 128, 64, 64),    # single q block
    (1, 192, 3, 1, 8, 64, 64),     # odd head count, 3 kv blocks
]


@pytest.mark.parametrize("B,S,H,KV,hd,bq,bk", SHAPES)
@pytest.mark.parametrize("causal", [True, False])
def test_flash_vs_ref(B, S, H, KV, hd, bq, bk, causal):
    q, k, v = _qkv(jax.random.PRNGKey(B * S + H), B, S, H, KV, hd, jnp.float32)
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                          interpret=True)
    exp = ref.attention_full(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", [16, 64, 100])
def test_flash_window(window):
    q, k, v = _qkv(jax.random.PRNGKey(7), 1, 256, 4, 2, 32, jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=window,
                          block_q=64, block_k=64, interpret=True)
    exp = ref.attention_full(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_dtypes(dtype):
    q, k, v = _qkv(jax.random.PRNGKey(3), 2, 128, 4, 4, 32, dtype)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    exp = ref.attention_full(q, k, v, causal=True)
    assert out.dtype == dtype
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("window", [None, 48])
def test_chunked_vs_full(causal, window):
    q, k, v = _qkv(jax.random.PRNGKey(5), 2, 256, 4, 2, 32, jnp.float32)
    out = ref.attention_chunked(q, k, v, causal=causal, window=window, chunk=64)
    exp = ref.attention_full(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-5, rtol=2e-5)


def test_chunked_traced_window():
    """Traced window scalars (the scanned hybrid-stack path) must match."""
    q, k, v = _qkv(jax.random.PRNGKey(9), 1, 128, 4, 4, 16, jnp.float32)

    def f(w):
        return ref.attention_full(q, k, v, causal=True, window=w)

    out = jax.jit(f)(jnp.int32(32))
    exp = ref.attention_full(q, k, v, causal=True, window=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-6)
    # window = -1 means full
    out_full = jax.jit(f)(jnp.int32(-1))
    exp_full = ref.attention_full(q, k, v, causal=True, window=None)
    np.testing.assert_allclose(np.asarray(out_full), np.asarray(exp_full),
                               atol=2e-6)


def test_cross_attention_no_causal():
    kq, kk = jax.random.split(jax.random.PRNGKey(11))
    q = jax.random.normal(kq, (2, 32, 4, 16))
    k = jax.random.normal(kk, (2, 96, 2, 16))
    v = jax.random.normal(kk, (2, 96, 2, 16))
    out = ref.attention_chunked(q, k, v, causal=False, chunk=32)
    exp = ref.attention_full(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-5)


@pytest.mark.parametrize("impl", ["full", "chunked"])
def test_value_head_narrower_than_query_key(impl):
    """Latent attention's widths: query/key heads of 192, value heads of
    128; scores scaled by 192^-0.5.  Against a per-head softmax written out
    in numpy (float64)."""
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(13), 3)
    S, H = 64, 2
    q = jax.random.normal(kq, (1, S, H, 192))
    k = jax.random.normal(kk, (1, S, H, 192))
    v = jax.random.normal(kv, (1, S, H, 128))
    if impl == "full":
        out = ref.attention_full(q, k, v, causal=True)
    else:
        out = ref.attention_chunked(q, k, v, causal=True, chunk=16)
    assert out.shape == (1, S, H, 128)
    qn, kn, vn = (np.asarray(a, np.float64)[0] for a in (q, k, v))
    s = np.einsum("qhd,khd->hqk", qn, kn) / np.sqrt(192)
    s = np.where(np.tril(np.ones((S, S), bool)), s, -np.inf)
    w = np.exp(s - s.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(out[0]),
                               np.einsum("hqk,khd->qhd", w, vn),
                               atol=2e-5, rtol=2e-5)
