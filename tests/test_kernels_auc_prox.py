"""auc_loss + prox_update Pallas kernels vs oracles and vs autodiff, with
hypothesis property sweeps on the paper's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.auc_loss import auc_loss
from repro.kernels.prox_update import prox_update


def _case(key, T, p_frac=0.5):
    kh, ky = jax.random.split(key)
    h = jax.random.uniform(kh, (T,))
    y = (jax.random.uniform(ky, (T,)) < p_frac).astype(jnp.float32)
    return h, y


@pytest.mark.parametrize("T,block", [(64, 32), (100, 32), (1024, 256),
                                     (7, 8), (513, 128)])
@pytest.mark.parametrize("p", [0.5, 0.71])
def test_auc_kernel_vs_ref(T, block, p):
    h, y = _case(jax.random.PRNGKey(T), T, p)
    a, b, alpha = 0.3, 0.2, -0.1
    got = auc_loss(h, y, a, b, alpha, p, block=block, interpret=True)
    exp = ref.auc_loss_ref(h, y, a, b, alpha, p)
    for g, e in zip(got, exp):
        np.testing.assert_allclose(np.asarray(g), np.asarray(e),
                                   atol=1e-5, rtol=1e-4)


def test_auc_ref_vs_autodiff():
    """Closed-form partials must equal autodiff of the direct F expression."""
    h, y = _case(jax.random.PRNGKey(1), 257, 0.6)
    p = 0.7

    def direct(h, a, b, alpha):
        pos = y
        neg = 1 - y
        f = ((1 - p) * (h - a) ** 2 * pos + p * (h - b) ** 2 * neg
             + 2 * (1 + alpha) * (p * h * neg - (1 - p) * h * pos)
             - p * (1 - p) * alpha ** 2)
        return jnp.mean(f)

    a, b, alpha = 0.4, 0.1, 0.25
    grads = jax.grad(direct, argnums=(0, 1, 2, 3))(h, a, b, alpha)
    loss, dh, da, db, dalpha = ref.auc_loss_ref(h, y, a, b, alpha, p)
    np.testing.assert_allclose(np.asarray(loss), direct(h, a, b, alpha), atol=1e-6)
    np.testing.assert_allclose(np.asarray(dh), np.asarray(grads[0]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(da), np.asarray(grads[1]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(db), np.asarray(grads[2]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(dalpha), np.asarray(grads[3]), atol=1e-6)


@settings(max_examples=25, deadline=None)
@given(p=st.floats(0.05, 0.95), alpha=st.floats(-2.0, 2.0),
       seed=st.integers(0, 2 ** 16))
def test_auc_strong_concavity_in_alpha(p, alpha, seed):
    """F is 2p(1-p)-strongly concave in α (the paper's μ_α): the closed-form
    α* = E[h|−]−E[h|+] maximizes it."""
    h, y = _case(jax.random.PRNGKey(seed), 128, 0.5)
    if float(y.sum()) in (0.0, 128.0):
        return
    f = lambda al: ref.auc_loss_ref(h, y, 0.1, 0.2, al, p)[0]
    # NOTE F uses prior p while α* uses the batch composition; with the exact
    # gradient condition: dF/dα(α_opt)=0 where α_opt solves the p-weighted
    # problem.  Check concavity + stationarity of the p-weighted optimum.
    g = jax.grad(f)
    alpha_opt = float(jnp.sum(2 * (p * h * (1 - y) - (1 - p) * h * y)) /
                      (2 * p * (1 - p) * h.shape[0]))
    assert abs(float(g(alpha_opt))) < 1e-4
    assert float(f(alpha_opt)) >= float(f(alpha)) - 1e-5


@pytest.mark.parametrize("N,block", [(128, 64), (1000, 256), (5, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_prox_kernel_vs_ref(N, block, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(N), 3)
    v = jax.random.normal(k1, (N,), dtype)
    g = jax.random.normal(k2, (N,), dtype)
    v0 = jax.random.normal(k3, (N,), dtype)
    got = prox_update(v, g, v0, 0.05, 0.5, block=block, interpret=True)
    exp = ref.prox_update_ref(v, g, v0, 0.05, 0.5)
    assert got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(exp, np.float32), atol=2e-2
                               if dtype == jnp.bfloat16 else 1e-6)


# small stand-ins for every leaf kind of the benchmark's configurations, as
# the update sees them (the worker axis leading); most are large enough to
# take several ~1 MiB blocks, with a partial block at the edge
_TILED_CASES = {
    "embedding-rank2": ((512, 2048), jnp.float32, False),
    "stacked-rank3": ((2, 256, 1536), jnp.float32, False),
    "stacked-norms-6-rows": ((1, 6, 256), jnp.float32, False),
    "two-squeezed-axes": ((2, 2, 300, 1000), jnp.float32, False),
    "conv-64-lanes": ((3, 3, 512, 64), jnp.float32, False),
    "conv-256-lanes": ((3, 3, 128, 256), jnp.float32, False),
    "score-head-2048x1": ((2048, 1), jnp.float32, False),
    "rank1": ((100,), jnp.float32, False),
    "vmapped-K4-conv": ((4, 3, 3, 128, 256), jnp.float32, True),
    "bf16": ((600, 2048), jnp.bfloat16, False),
    "ragged-rows": ((300, 2048), jnp.float32, False),
}


@pytest.mark.parametrize("case", list(_TILED_CASES))
def test_prox_kernel_own_layout_vs_ref_and_flat(case):
    """A leaf updated in its own shape equals the reference, and equals the
    flat launch over the same elements bit for bit (same body, same f32
    expression)."""
    shape, dtype, vmapped = _TILED_CASES[case]
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(len(case)), 3)
    v, g, v0 = (jax.random.normal(k, shape, dtype) for k in (k1, k2, k3))
    upd = lambda v, g, v0: prox_update(v, g, v0, 0.05, 0.5, interpret=True)
    if vmapped:
        upd = jax.vmap(upd)
    got = upd(v, g, v0)
    flat = prox_update(v.reshape(-1), g.reshape(-1), v0.reshape(-1), 0.05,
                       0.5, block=1 << 16, interpret=True).reshape(shape)
    exp = ref.prox_update_ref(v, g, v0, 0.05, 0.5)
    assert got.shape == shape and got.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(flat, np.float32))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(exp, np.float32), atol=2e-2
                               if dtype == jnp.bfloat16 else 1e-6)


@settings(max_examples=30, deadline=None)
@given(eta=st.floats(1e-4, 1.0), gamma=st.floats(1e-3, 10.0),
       seed=st.integers(0, 2 ** 16))
def test_prox_is_argmin(eta, gamma, seed):
    """The update must minimize u ↦ g·u + ‖u−v‖²/(2η) + ‖u−v₀‖²/(2γ)
    (footnote 1 of the paper) — verify the first-order condition."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    v = jax.random.normal(k1, (16,))
    g = jax.random.normal(k2, (16,))
    v0 = jax.random.normal(k3, (16,))
    u = ref.prox_update_ref(v, g, v0, eta, gamma)
    foc = g + (u - v) / eta + (u - v0) / gamma
    # fp32 roundoff in u is amplified by 1/η + 1/γ in the optimality residual
    tol = 3e-6 * (1 / eta + 1 / gamma) + 1e-5
    np.testing.assert_allclose(np.asarray(foc), 0.0, atol=tol)
