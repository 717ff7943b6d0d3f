"""Sort-based dropless MoE dispatch (models/moe.py ``dispatch="sorted"``).

Pins: (1) sorted dispatch ≡ a dense one-hot einsum oracle, forward AND
gradient, in fp32; (2) sorted ≡ the ``capacity`` path whenever nothing
drops (eval C = T is dropless by construction); (3) the grouped-GEMM Pallas
kernel ≡ its blocked-scan jnp reference on ragged/empty/unaligned segments;
(4) the all-k load-balance aux loss reduces to the classic top-1 count at
k = 1 and actually counts both slots at k = 2; (5) the dispatch-buffer
accounting the moe_dispatch benchmark reports; (6) a held share's sorted
dispatch at its tight row bound ≡ the full bound, values and gradients,
with routing inside the tight bound and routing forced over it (the
fallback), and its VJP saves nothing at the full bound."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.configs import get_smoke_config
from repro.configs.base import ModelConfig, MoEConfig
from repro.kernels import ref
from repro.kernels.moe_dispatch import grouped_matmul
from repro.models import moe


def _moe_cfg(E, k, d=16, ff=32, dispatch="sorted", capacity_factor=1.25):
    return ModelConfig(
        name="tiny-moe", family="moe", n_layers=1, d_model=d, n_heads=2,
        n_kv_heads=2, d_ff=ff, vocab_size=64,
        moe=MoEConfig(n_experts=E, top_k=k, dispatch=dispatch,
                      capacity_factor=capacity_factor))


def _dense_oracle(cfg, p, x):
    """Dense one-hot einsum MoE: every expert sees every token, combine
    weights select — the O(E·T) semantics oracle for any dispatch."""
    m = cfg.moe
    B, S, d = x.shape
    xf = x.reshape(B * S, d)
    top_g, top_e, _ = moe.route(cfg, p, xf)
    comb = jnp.sum(jax.nn.one_hot(top_e, m.n_experts, dtype=jnp.float32)
                   * top_g[..., None], axis=1)              # [T, E]
    h = jnp.einsum("td,edf->tef", xf, p["w_gate"])
    u = jnp.einsum("td,edf->tef", xf, p["w_up"])
    ye = jnp.einsum("tef,efd->ted", jax.nn.silu(h) * u, p["w_down"])
    out = jnp.einsum("te,ted->td", comb, ye)
    return out.reshape(B, S, d).astype(x.dtype)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2 ** 16), E=st.sampled_from([2, 4, 8]),
       k=st.integers(1, 3), T=st.integers(1, 33))
def test_sorted_matches_dense_oracle_forward_and_grad(seed, E, k, T):
    cfg = _moe_cfg(E, min(k, E))
    key = jax.random.PRNGKey(seed)
    kp, kx = jax.random.split(key)
    p = moe.init_moe(kp, cfg)
    x = jax.random.normal(kx, (1, T, cfg.d_model), jnp.float32) * 0.5

    got, _ = moe.apply_moe(cfg, p, x)
    want = _dense_oracle(cfg, p, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)

    # gradient w.r.t. inputs and every expert weight (fp32)
    tang = jax.random.normal(kx, got.shape)
    g_got = jax.grad(lambda p, x: jnp.sum(moe.apply_moe(cfg, p, x)[0] * tang),
                     argnums=(0, 1))(p, x)
    g_want = jax.grad(lambda p, x: jnp.sum(_dense_oracle(cfg, p, x) * tang),
                      argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree_util.tree_leaves(g_got),
                    jax.tree_util.tree_leaves(g_want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2 ** 16), arch=st.sampled_from(
    ["dbrx-132b", "arctic-480b"]), T=st.integers(1, 40))
def test_sorted_matches_capacity_when_dropless(seed, arch, T):
    """Eval-mode capacity dispatch (C = T) never drops, so the two modes
    must agree on identical routing decisions."""
    cfg = get_smoke_config(arch)
    cfg_cap = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, dispatch="capacity"))
    key = jax.random.PRNGKey(seed)
    p = moe.init_moe(key, cfg)
    x = jax.random.normal(key, (2, T, cfg.d_model), jnp.float32) * 0.5
    got, aux_s = moe.apply_moe(cfg, p, x)
    want, aux_c = moe.apply_moe(cfg_cap, p, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    assert float(aux_s) == float(aux_c)  # routing (and aux) bitwise-shared


def _bf16(a):
    """The kernel multiplies in one bf16 MXU pass: the reference is given the
    same bf16-rounded operands, whose products fp32 holds exactly, so the
    fp32 tolerances below still bound only the summation order."""
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def test_grouped_matmul_kernel_matches_ref():
    """Interpret-mode Pallas kernel vs the jnp reference on ragged segments:
    empty experts, tile-unaligned sizes, trailing empty groups."""
    key = jax.random.PRNGKey(0)
    for gs in ([3, 0, 6, 1], [0, 0, 10, 0], [10, 0, 0, 0], [1, 2, 3, 4]):
        gs = jnp.asarray(gs, jnp.int32)
        N = int(gs.sum())
        kx, kw, key = jax.random.split(key, 3)
        x = jax.random.normal(kx, (N, 7))
        w = jax.random.normal(kw, (4, 7, 5))
        want = ref.grouped_matmul_ref(_bf16(x), _bf16(w), gs)
        got = grouped_matmul(x, w, gs, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)


def test_grouped_matmul_kernel_small_blocks():
    """Block sizes smaller than segments force multi-tile experts."""
    key = jax.random.PRNGKey(1)
    gs = jnp.asarray([5, 9, 0, 2], jnp.int32)
    x = jax.random.normal(key, (16, 4))
    w = jax.random.normal(key, (4, 4, 6))
    want = ref.grouped_matmul_ref(_bf16(x), _bf16(w), gs)
    got = grouped_matmul(x, w, gs, block_m=8, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_aux_loss_k1_equals_top1_count():
    """At k = 1 the all-k dispatched-fraction count must equal the classic
    Switch top-1 formulation exactly."""
    cfg = _moe_cfg(4, 1)
    key = jax.random.PRNGKey(2)
    p = moe.init_moe(key, cfg)
    x = jax.random.normal(key, (2, 16, cfg.d_model))
    _, aux = moe.apply_moe(cfg, p, x)
    xf = x.reshape(-1, cfg.d_model)
    _, top_e, gates = moe.route(cfg, p, xf)
    me = jnp.mean(gates, axis=0)
    ce_top1 = jnp.mean(jax.nn.one_hot(top_e[:, 0], 4, dtype=jnp.float32),
                       axis=0)
    want = 4 * jnp.sum(me * ce_top1)
    assert float(aux) == pytest.approx(float(want), abs=0)


def test_aux_loss_counts_all_k_slots():
    """A router biased to always pick experts {0, 1} as the top-2 pair must
    report HALF the dispatch mass on each — the slot-0-only count would
    blame only the argmax expert."""
    cfg = _moe_cfg(4, 2)
    key = jax.random.PRNGKey(3)
    p = moe.init_moe(key, cfg)
    p = dict(p, router=jnp.zeros_like(p["router"]).at[:, 0].set(1.0)
             .at[:, 1].set(0.999))  # every token routes to (0, 1)
    x = jnp.abs(jax.random.normal(key, (1, 32, cfg.d_model))) + 0.5
    xf = x.reshape(-1, cfg.d_model)
    _, top_e, _ = moe.route(cfg, p, xf)
    assert set(np.unique(np.asarray(top_e))) == {0, 1}
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(top_e, 4, dtype=jnp.float32), axis=1),
                  axis=0) / 2
    np.testing.assert_allclose(np.asarray(ce), [0.5, 0.5, 0.0, 0.0],
                               atol=1e-6)


def test_dispatch_buffer_bytes_accounting():
    """The acceptance numbers: sorted = T·k·d vs capacity C=T = E·T·d —
    an E/top_k-fold gap (64× on the real arctic-480b config, well past the
    required E/(2·top_k))."""
    from repro.configs import get_config
    cfg = get_config("arctic-480b")
    T = 32768
    s = moe.dispatch_buffer_bytes(cfg, T, mode="sorted")
    c = moe.dispatch_buffer_bytes(cfg, T, mode="capacity")
    E, k = cfg.moe.n_experts, cfg.moe.top_k
    assert s == T * k * cfg.d_model * 4
    assert c == E * moe.capacity(cfg, T, train=False) * cfg.d_model * 4
    assert c / s >= E / (2 * k)
    with pytest.raises(ValueError):
        moe.dispatch_buffer_bytes(cfg, T, mode="dense")


def test_moe_config_rejects_unknown_dispatch():
    with pytest.raises(ValueError):
        MoEConfig(n_experts=4, top_k=2, dispatch="scatter")


def test_prefill_matches_parallel_scoring_moe():
    """Token-by-token prefill through serve_step (sorted dispatch at T = B
    per step) must reproduce the parallel forward's last-token logits."""
    from repro.models import init_params
    from repro.models import model as M
    from repro.serving import decode as D
    cfg = get_smoke_config("dbrx-132b")
    assert cfg.moe.dispatch == "sorted"
    key = jax.random.PRNGKey(4)
    params = init_params(key, cfg)
    tokens = jax.random.randint(key, (2, 12), 0, cfg.vocab_size)
    cache = D.init_cache(cfg, 2, 12, use_window=False, dtype=jnp.float32)
    _, got = D.prefill(cfg, params, cache, tokens, use_window=False)
    h, _ = M.backbone(cfg, params, {"tokens": tokens})
    want = M.lm_logits(cfg, params, h[:, -1])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-3, rtol=2e-3)


# A held share (experts 2 and 3 of 8, 3 per token) at T = 32: full bound
# T·min(k, E_held) = 64 rows, tight bound 2 · T·k·E_held/E = 48 rows.
HELD_T = 32


def _held_cfg():
    return ModelConfig(
        name="tiny-held", family="moe", n_layers=1, d_model=16, n_heads=2,
        n_kv_heads=2, d_ff=32, vocab_size=64,
        moe=MoEConfig(n_experts=8, top_k=3, n_held=2, held_offset=2,
                      scoring="sigmoid"))


def _held_case(routing):
    """Weights and input; ``over``: a selection bias that sends every token
    to both held experts, more rows than the tight bound holds."""
    cfg = _held_cfg()
    kp, kx = jax.random.split(jax.random.PRNGKey(7))
    p = moe.init_moe(kp, cfg)
    if routing == "over":
        p["select_bias"] = p["select_bias"].at[2:4].set(5.0)
    x = jax.random.normal(kx, (1, HELD_T, cfg.d_model), jnp.float32)
    return cfg, p, x


def test_held_share_bounds():
    cfg = _held_cfg()
    assert moe.held_rows(cfg, HELD_T) == 64
    assert moe.tight_rows(cfg, HELD_T) == 48
    # every expert held: the tight bound is no tighter, one path
    full = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, n_held=8, held_offset=0))
    assert moe.tight_rows(full, HELD_T) == moe.held_rows(full, HELD_T)


@pytest.mark.parametrize("impl", ["ref", "pallas"])
@pytest.mark.parametrize("routing", ["inside", "over"])
def test_tight_bound_matches_full_bound(monkeypatch, routing, impl):
    """Output and every gradient (the input, the three expert weights, the
    router through the gate weights) of the held share's dispatch at its
    tight bound equal those of the full bound alone; routed over the tight
    bound, the fallback gives them."""
    cfg, p, x = _held_case(routing)
    _, top_e, _ = moe.route(cfg, p, x.reshape(HELD_T, -1))
    rows = int(jnp.sum((top_e >= 2) & (top_e < 4)))
    assert (rows <= 48) == (routing == "inside"), rows
    tang = jax.random.normal(jax.random.PRNGKey(8), x.shape)

    def value_and_grads():
        f = lambda p, x: jnp.sum(
            moe.apply_moe(cfg, p, x, train=True, impl=impl)[0] * tang)
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(p, x)

    got, g_got = value_and_grads()
    monkeypatch.setattr(moe, "tight_rows", moe.held_rows)
    want, g_want = value_and_grads()
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    names = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_flatten_with_path(g_want)[0]]
    for name, a, b in zip(names, jax.tree_util.tree_leaves(g_got),
                          jax.tree_util.tree_leaves(g_want)):
        scale = max(float(jnp.max(jnp.abs(b))), 1e-6)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=1e-5 * scale, err_msg=name)
    for w in ("router", "w_gate", "w_up", "w_down"):
        assert float(jnp.max(jnp.abs(g_want[0][w]))) > 0, w


@pytest.mark.parametrize("routing", ["inside", "over"])
def test_tight_bound_saves_nothing_at_the_full_bound(routing):
    """The dispatch's VJP keeps its inputs and the [T·k] routing arrays: no
    saved array has the full bound's rows (64) or its padded grouped-GEMM
    layout's (64 + 2 · 64), whichever bound the routing takes."""
    cfg, p, x = _held_case(routing)
    _, vjp = jax.vjp(lambda p, x: moe.apply_moe(cfg, p, x, train=True)[0],
                     p, x)
    saved = [a.shape for a in jax.tree_util.tree_leaves(vjp)]
    assert saved
    full = {64, 64 + 2 * 64}
    assert not [s for s in saved if s and s[0] in full], saved
