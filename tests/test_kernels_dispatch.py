"""Backend dispatch for the Pallas kernel wrappers (kernels/ops.py).

The bug this pins down: a non-TPU backend must NEVER be handed
interpret-mode Pallas by the "auto" path — interpret mode is a correctness
tool, orders of magnitude slower than either a real kernel or the jnp
reference, so "auto" routes every non-TPU backend to kernels/ref.py and
only the explicit ``impl="pallas"`` override may interpret off-TPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("backend,impl,want", [
    # (use_pallas, interpret) per (backend, impl)
    ("tpu", "auto", (True, False)),     # real kernel on TPU
    ("gpu", "auto", (False, False)),    # GPU: XLA reference, NOT interpret
    ("cpu", "auto", (False, False)),    # CPU: XLA reference
    ("tpu", "pallas", (True, False)),
    ("gpu", "pallas", (True, True)),    # explicit override only
    ("cpu", "pallas", (True, True)),
    ("tpu", "ref", (False, False)),
    ("cpu", "ref", (False, False)),
])
def test_dispatch_per_backend(monkeypatch, backend, impl, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert ops.dispatch(impl) == want


def test_dispatch_rejects_unknown_impl():
    with pytest.raises(ValueError):
        ops.dispatch("mosaic")
    with pytest.raises(ValueError):
        ops.dispatch("")


def test_auto_never_traces_pallas_off_tpu(monkeypatch):
    """On a simulated GPU backend, the auto wrappers must produce the
    reference results without touching the Pallas kernels at all — if the
    kernel were traced (even in interpret mode) the sentinel would fire."""
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")

    def boom(*a, **k):
        raise AssertionError("auto dispatched Pallas off-TPU")

    monkeypatch.setattr(ops, "_flash", boom)
    monkeypatch.setattr(ops, "_auc_kernel", boom)
    monkeypatch.setattr(ops, "_prox_kernel", boom)

    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 8, 2, 4))
    k = jax.random.normal(key, (1, 8, 1, 4))
    o = ops.attention(q, k, k, causal=True, impl="auto")
    np.testing.assert_allclose(
        np.asarray(o), np.asarray(ref.attention_full(q, k, k, causal=True)),
        atol=1e-6)

    h = jax.random.uniform(key, (64,))
    y = (jax.random.uniform(key, (64,)) < 0.7).astype(jnp.float32)
    got = ops.auc_loss(h, y, 0.1, 0.2, 0.0, 0.7, impl="auto")
    want = ref.auc_loss_ref(h, y, 0.1, 0.2, 0.0, 0.7)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-6)

    v = jax.random.normal(key, (32,))
    got = ops.prox_update_tree({"w": v}, {"w": v}, {"w": v}, 0.1, 0.5,
                               impl="auto")
    np.testing.assert_allclose(
        np.asarray(got["w"]),
        np.asarray(ref.prox_update_ref(v, v, v, 0.1, 0.5)), atol=1e-6)


def test_auto_never_traces_grouped_matmul_off_tpu(monkeypatch):
    """Same invariant for the sorted-dispatch grouped GEMM: "auto" on a
    non-TPU backend must reach the blocked-scan jnp reference, never the
    (interpret-mode) Pallas kernel."""
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")

    def boom(*a, **k):
        raise AssertionError("auto dispatched the grouped-GEMM Pallas "
                             "kernel off-TPU")

    monkeypatch.setattr(ops, "_grouped_kernel", boom)
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (6, 4))
    w = jax.random.normal(key, (3, 4, 8))
    gs = jnp.asarray([2, 3, 1], jnp.int32)
    got = ops.grouped_matmul(x, w, gs, impl="auto")
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref.grouped_matmul_ref(x, w, gs)),
        atol=1e-6)


def test_explicit_pallas_grouped_matmul_interprets_off_tpu():
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (6, 4))
    w = jax.random.normal(key, (3, 4, 8))
    gs = jnp.asarray([2, 3, 1], jnp.int32)
    got = ops.grouped_matmul(x, w, gs, impl="pallas")
    # the kernel multiplies in one bf16 pass: the reference gets the same
    # bf16-rounded operands, whose products fp32 holds exactly
    bf = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref.grouped_matmul_ref(bf(x), bf(w), gs)),
        atol=1e-5)


def test_explicit_pallas_interprets_off_tpu():
    """impl="pallas" off-TPU is the deliberate interpret-mode escape hatch
    and must still agree with the reference."""
    key = jax.random.PRNGKey(1)
    v = jax.random.normal(key, (64,))
    got = ops.prox_update_tree({"w": v}, {"w": v}, {"w": v}, 0.1, 0.5,
                               impl="pallas")
    np.testing.assert_allclose(
        np.asarray(got["w"]),
        np.asarray(ref.prox_update_ref(v, v, v, 0.1, 0.5)), atol=1e-5)


def test_coda_impl_reaches_auc_loss_and_ref_matches_pallas_window(monkeypatch):
    """``CoDAConfig.impl`` must reach the fused AUC loss inside a training
    window: impl="pallas" traces the (interpret-mode) AUC kernel, impl="ref"
    never does, and the two windows agree on params and duals."""
    from repro.configs.base import mlp_config
    from repro.core import coda

    mcfg = mlp_config(n_features=16, d=32)
    K, I, B = 2, 2, 16
    ky, kx = jax.random.split(jax.random.PRNGKey(4))
    y = (jax.random.uniform(ky, (I, K, B)) < 0.7).astype(jnp.float32)
    x = jax.random.normal(kx, (I, K, B, 16)) + 0.3 * (y[..., None] * 2 - 1)
    wb = {"features": x, "labels": y}

    calls = []
    real = ops._auc_kernel

    def counting(*a, **k):
        calls.append(k.get("interpret"))
        return real(*a, **k)

    monkeypatch.setattr(ops, "_auc_kernel", counting)
    out = {}
    for impl in ("ref", "pallas"):
        del calls[:]
        ccfg = coda.CoDAConfig(n_workers=K, p_pos=0.7, impl=impl)
        st0 = coda.init_state(jax.random.PRNGKey(0), mcfg, ccfg)
        out[impl], _ = coda.window_step(mcfg, ccfg, st0, wb, 0.1)
        assert (len(calls) > 0) == (impl == "pallas"), (impl, calls)
    for part in ("params", "duals"):
        for a, b in zip(jax.tree_util.tree_leaves(out["ref"][part]),
                        jax.tree_util.tree_leaves(out["pallas"][part])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
