"""Moonlight-16B-A3B's layers against the plain float32 reference of its
benchmark configuration (``benchmarks/chip/configs/moonlight-16b-a3b-share.py``)
at a small size on the CPU, with seeded weights: latent attention, the
sigmoid router with its selection bias, the shared experts, the dense
prefix and the held expert share, values and gradients; the share-sum
identity that ties the share to the uncut layer; the grouped GEMM's
backward kernels (interpret mode) against the jnp reference; the share's
launch geometry; the row bound that workers vmapped together take."""
import dataclasses
import importlib.util
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ref
from repro.kernels.moe_dispatch import grouped_matmul
from repro.models import attention, model as M, moe
from repro.models.mlp import apply_mlp

CONF = pathlib.Path(__file__).resolve().parents[1] / "benchmarks/chip/configs"
_spec = importlib.util.spec_from_file_location(
    "moonlight_share_reference", CONF / "moonlight-16b-a3b-share.py")
REF = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(REF)
FULL = json.loads((CONF / "moonlight-16b-a3b-share.json").read_text())

# every width scaled down, the structure kept: one dense layer and two MoE
# layers, 8 routed experts of which 2 are held (experts 2 and 3), 3 per
# token, 2 shared; a selection bias large enough to move some choices
SMALL = dict(FULL, hidden_size=64, intermediate_size=96,
             num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=32,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             moe_intermediate_size=32, num_hidden_layers=3,
             n_routed_experts=2, held_offset=2, num_experts_per_tok=3,
             vocab_size=256, select_bias_std=0.1,
             published=dict(FULL["published"], n_routed_experts=8))
B, S = 2, 16
# Program and reference both run in float32 on the CPU; they associate
# differently (MLA's concatenated against split score products, the sorted
# grouped GEMM against the dense masked experts), so they agree to float32
# round-off: measured gaps are under 2e-6 (values of magnitude ~5) and
# 1.3e-6 of each leaf's largest gradient entry, and these limits leave ten
# to fifteen times that.  The reference in bfloat16 misses by 4e-3 (scores)
# and 0.14 (gradients).
ATOL_VALUE = 2e-5
GRAD_SHARE = 2e-5


def _weights(cfg=SMALL, seed=0):
    return REF.make_weights(jax.random.PRNGKey(seed), cfg)


def _tokens(seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (B, S), 0,
                              SMALL["vocab_size"])


def _x(seed=2, d=SMALL["hidden_size"]):
    return jax.random.normal(jax.random.PRNGKey(seed), (B, S, d))


def _layer(tree, i):
    return jax.tree_util.tree_map(lambda l: l[i], tree)


@jax.jit
def _program_score(p, tokens):
    return M.score(REF.model_config(SMALL), p, {"tokens": tokens},
                   train=True, impl="ref")[0]


@jax.jit
def _ref_score(p, tokens):
    return REF.score(p, {"tokens": tokens}, SMALL)


def _assert_grads_close(got, want):
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        scale = max(float(jnp.max(jnp.abs(w))), 1e-6)
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=GRAD_SHARE * scale, rtol=0,
                                   err_msg=jax.tree_util.keystr(path))


def test_program_config_and_weights_layout():
    mcfg = REF.model_config(SMALL)
    assert (mcfg.moe.n_experts, mcfg.moe.n_held, mcfg.moe.held_offset,
            mcfg.moe.top_k) == (8, 2, 2, 3)
    # the 2 shared experts: one always-on SwiGLU of twice the expert width
    assert (mcfg.moe.dense_residual, mcfg.moe.dense_d_ff) == (True, 2 * 32)
    assert mcfg.dense_layers == 1 and mcfg.head_dim == 24
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    mine = jax.eval_shape(lambda k: REF.make_weights(k, SMALL), key)
    theirs = jax.eval_shape(lambda k: M.init_params(k, mcfg), key)
    assert jax.tree_util.tree_structure(mine) == \
        jax.tree_util.tree_structure(theirs)
    assert [l.shape for l in jax.tree_util.tree_leaves(mine)] == \
        [l.shape for l in jax.tree_util.tree_leaves(theirs)]


def _value_and_grads(f, *args, tang):
    """f's value and the gradient of <f, tang> in every argument, in one
    compiled program."""
    def loss(*a):
        y = f(*a)
        return jnp.sum(y * tang), y
    (_, y), g = jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(len(args))), has_aux=True))(*args)
    return y, g


def test_score_and_every_gradient_match_reference():
    """The whole network (dense prefix, MLA, router, held experts through
    the sorted dropless dispatch, shared experts, score head): scores and
    the gradient of every leaf."""
    p, toks = _weights(), _tokens()
    tang = jax.random.normal(jax.random.PRNGKey(3), (B,))
    got, g_prog = _value_and_grads(lambda p: _program_score(p, toks), p,
                                   tang=tang)
    want, g_ref = _value_and_grads(lambda p: _ref_score(p, toks), p,
                                   tang=tang)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=ATOL_VALUE, rtol=0)
    _assert_grads_close(g_prog, g_ref)


def test_mla_block_matches_reference():
    p = _layer(_weights()["layers"]["attn"], 0)
    mcfg, x = REF.model_config(SMALL), _x()
    pos = jnp.arange(S)[None, :]
    tang = jax.random.normal(jax.random.PRNGKey(4), x.shape)
    got, g_prog = _value_and_grads(lambda p, x: attention.attend(
        mcfg, p, x, pos, window=None), p, x, tang=tang)
    want, g_ref = _value_and_grads(lambda p, x: REF._mla(x, p, SMALL), p, x,
                                   tang=tang)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=ATOL_VALUE, rtol=0)
    _assert_grads_close(g_prog, g_ref)


def _dense_gates(top_g, top_e, E):
    rows = jnp.arange(top_e.shape[0])[:, None]
    return jnp.zeros((top_e.shape[0], E)).at[rows, top_e].set(top_g)


def test_router_selection_and_weights_match_reference():
    m = _layer(_weights()["layers"]["moe"], 0)
    xf = _x().reshape(B * S, -1)
    top_g, top_e, _ = moe.route(REF.model_config(SMALL), m, xf)
    want = REF.route(xf, m, SMALL)
    got = _dense_gates(top_g, top_e, 8)
    np.testing.assert_array_equal(np.asarray(got > 0), np.asarray(want > 0))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=ATOL_VALUE, rtol=0)
    # the weights are the unbiased scores renormalised and scaled
    np.testing.assert_allclose(np.asarray(jnp.sum(got, axis=1)), 2.446,
                               rtol=1e-6)
    # the bias moves the choice of some tokens here (it would be untested
    # otherwise)
    unbiased = REF.route(xf, dict(m, select_bias=jnp.zeros_like(
        m["select_bias"])), SMALL)
    assert bool(jnp.any((unbiased > 0) != (want > 0)))


def test_moe_layer_with_shared_experts_matches_reference():
    m = _layer(_weights()["layers"]["moe"], 1)
    mcfg, x = REF.model_config(SMALL), _x(5)
    aux = moe.apply_moe(mcfg, m, x, train=True, impl="ref")[1]
    assert float(aux) == 0.0  # no Switch loss beside the selection bias
    tang = jax.random.normal(jax.random.PRNGKey(6), x.shape)
    got, g_prog = _value_and_grads(lambda m, x: moe.apply_moe(
        mcfg, m, x, train=True, impl="ref")[0], m, x, tang=tang)
    want, g_ref = _value_and_grads(lambda m, x: REF._moe(x, m, SMALL), m, x,
                                   tang=tang)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=ATOL_VALUE, rtol=0)
    assert float(jnp.max(jnp.abs(g_prog[0]["select_bias"]))) == 0.0
    _assert_grads_close(g_prog, g_ref)


def test_shares_sum_to_the_uncut_layer():
    """The routed parts of all E / E_held shares, with the shared experts
    counted once, add up to the uncut reference layer."""
    uncut = dict(SMALL, n_routed_experts=8, held_offset=0)
    m = _layer(_weights(uncut)["layers"]["moe"], 0)
    x = _x(7)
    def shares(m, x):
        total = -3 * apply_mlp(REF.model_config(SMALL), m["dense"], x)
        for off in range(0, 8, 2):
            share = dict(SMALL, held_offset=off)
            held = {k: (v[off:off + 2] if k in ("w_gate", "w_up", "w_down")
                        else v) for k, v in m.items()}
            total = total + moe.apply_moe(REF.model_config(share), held, x,
                                          train=True, impl="ref")[0]
        return total

    np.testing.assert_allclose(
        np.asarray(jax.jit(shares)(m, x)),
        np.asarray(jax.jit(lambda m, x: REF._moe(x, m, uncut))(m, x)),
        atol=ATOL_VALUE, rtol=0)


@pytest.mark.parametrize("fault", ["select_without_bias", "no_routed_part"])
def test_faulty_reference_fails_the_tolerance(fault):
    """A reference that chooses without the bias, or that leaves out the
    held experts' part, is told apart from the program by the tolerance the
    tests above hold the program to."""
    p, toks = _weights(), _tokens()
    bad = p["layers"]["moe"]
    if fault == "select_without_bias":
        bad = dict(bad, select_bias=jnp.zeros_like(bad["select_bias"]))
    else:
        bad = dict(bad, w_down=jnp.zeros_like(bad["w_down"]))
    p_bad = dict(p, layers=dict(p["layers"], moe=bad))
    gap = jnp.max(jnp.abs(_program_score(p, toks) - _ref_score(p_bad, toks)))
    assert float(gap) > 10 * ATOL_VALUE


# ---------------------------------------------------------------------------
# grouped GEMM kernels: forward, dx and dw
# ---------------------------------------------------------------------------
def _bf16(a):
    """The kernels multiply in one bf16 MXU pass: the reference gets the
    same bf16-rounded operands (and cotangent), whose products fp32 holds
    exactly, so the fp32 tolerance bounds only the summation order."""
    return a.astype(jnp.bfloat16).astype(jnp.float32)


@pytest.mark.parametrize("counts,offset,held,rows", [
    ([3, 9, 0, 14, 5, 2], 2, 3, 40),   # experts 2-4 held, an empty one, dead tiles
    ([4, 4, 4, 4], 1, 2, 24),          # whole tiles
    ([6, 0, 0, 6], 1, 2, 16),          # nothing routed here: every tile dead
])
def test_grouped_matmul_vjp_matches_reference(counts, offset, held, rows):
    """The routed rows of a held share (``counts[offset:offset + held]``)
    lead a buffer at a static row bound; the rest are dead.  Row tiles of
    8 give experts several tiles and leave dead tiles to skip."""
    gs = jnp.asarray(counts[offset:offset + held], jnp.int32)
    kx, kw, kt = jax.random.split(jax.random.PRNGKey(sum(counts)), 3)
    x = _bf16(jax.random.normal(kx, (rows, 24)))
    w = _bf16(jax.random.normal(kw, (held, 24, 136)))
    tang = _bf16(jax.random.normal(kt, (rows, 136)))
    got, g_kernel = _value_and_grads(lambda x, w: grouped_matmul(
        x, w, gs, block_m=8, interpret=True), x, w, tang=tang)
    want, g_ref = _value_and_grads(
        lambda x, w: ref.grouped_matmul_ref(x, w, gs), x, w, tang=tang)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-5)
    assert float(jnp.max(jnp.abs(got[int(gs.sum()):]))) == 0.0
    for g, r in zip(g_kernel, g_ref):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=1e-4, rtol=1e-5)


def test_grouped_matmul_vjp_under_vmap():
    """The executor vmaps the local step over its workers: the kernels'
    scalar-prefetch operands batch with it."""
    gs = jnp.asarray([[5, 0, 7], [0, 12, 1]], jnp.int32)
    kx, kw, kt = jax.random.split(jax.random.PRNGKey(9), 3)
    x = _bf16(jax.random.normal(kx, (2, 16, 8)))
    w = _bf16(jax.random.normal(kw, (2, 3, 8, 128)))
    tang = _bf16(jax.random.normal(kt, (2, 16, 128)))
    f = lambda fn: jax.jit(jax.vmap(jax.grad(
        lambda x, w, g, t: jnp.sum(fn(x, w, g) * t), argnums=(0, 1))))(
            x, w, gs, tang)
    got = f(lambda x, w, g: grouped_matmul(x, w, g, block_m=8,
                                           interpret=True))
    want = f(ref.grouped_matmul_ref)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=1e-4, rtol=1e-5)


def test_share_geometry_of_the_cell():
    """One MoE layer of the benchmark cell: 4 x 2048 tokens, 6 of 64
    experts per token, 8 held."""
    cfg = REF.model_config(FULL)
    g = moe.share_geometry(cfg, 4 * 2048)
    assert g["row_bound"] == 8192 * 6
    # 2 x the even router's 6,144 rows, a multiple of the 128-row tile
    assert g["tight_rows"] == 12288
    assert g["expected_rows"] == 6144
    assert g["bm"] == 128
    assert g["padded_rows"] == 8192 * 6 + 8 * 128
    assert g["grid"] == (1, (8192 * 6 + 8 * 128) // 128)
    # every expert held, k <= E: the whole sorted buffer, as before
    full = get_config("dbrx-132b")
    assert moe.share_geometry(full, 100)["row_bound"] == 100 * 4
    assert moe.share_geometry(full, 100)["tight_rows"] == 100 * 4


@pytest.mark.parametrize("over", [(), (1,)], ids=["all_fit", "one_over"])
def test_vmapped_workers_take_one_bound(over):
    """The executor vmaps the local step over its workers: they take the
    tight row bound together, or all fall back to the full bound when one
    routes more rows than the tight bound holds; each worker's layer value
    and gradients are its own either way."""
    cfg = REF.model_config(SMALL)
    T = B * S
    assert moe.tight_rows(cfg, T) < moe.held_rows(cfg, T)
    p = _layer(_weights()["layers"], 0)["moe"]
    ps = [p, dict(p, router=p["router"] * 0.5)]
    for w in over:   # every token to both held experts: 2T rows
        ps[w] = dict(ps[w], select_bias=ps[w]["select_bias"].at[2:4].set(5.0))
    x = _x()
    for w, pw in enumerate(ps):
        _, top_e, _ = moe.route(cfg, pw, (x + w).reshape(T, -1))
        rows = int(jnp.sum((top_e >= 2) & (top_e < 4)))
        assert (rows > moe.tight_rows(cfg, T)) == (w in over), rows
    tang = jax.random.normal(jax.random.PRNGKey(4), x.shape)

    def f(p, x):
        return jnp.sum(moe.apply_moe(cfg, p, x, train=True, impl="ref")[0]
                       * tang)

    vg = jax.value_and_grad(f, argnums=(0, 1))
    stack = lambda *t: jax.tree_util.tree_map(lambda *a: jnp.stack(a), *t)
    got, g_got = jax.jit(jax.vmap(vg))(stack(*ps), jnp.stack([x, x + 1]))
    for w, pw in enumerate(ps):
        want, g_want = jax.jit(vg)(pw, x + w)
        np.testing.assert_allclose(float(got[w]), float(want), rtol=1e-5)
        _assert_grads_close(jax.tree_util.tree_map(lambda a: a[w], g_got),
                            g_want)


def test_published_config_numbers():
    cfg = get_config("moonlight-16b-a3b")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.head_dim,
            cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.v_head_dim) == \
        (27, 2048, 16, 192, 512, 64, 128)
    assert (cfg.d_ff, cfg.dense_layers, cfg.dense_layers_d_ff,
            cfg.vocab_size) == (1408, 1, 11264, 163840)
    m = cfg.moe
    assert (m.n_experts, m.n_held, m.top_k, m.dense_residual, m.dense_d_ff,
            m.scoring, m.routed_scale) == \
        (64, 64, 6, True, 2 * 1408, "sigmoid", 2.446)
    share = REF.model_config(FULL)
    assert dataclasses.replace(share.moe, n_held=64, held_offset=0) == m
