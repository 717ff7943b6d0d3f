"""Grouped expert GEMM Pallas kernels for sort-based dropless MoE dispatch:
the forward and both halves of its backward.

``grouped_matmul(x, w, group_sizes)`` computes ``out[i] = x[i] @ w[g(i)]``
for rows already sorted by expert id — the ragged core of the sorted
dispatch path (models/moe.py) — without materializing the [E, C, d]
capacity buffer.  Rows past ``sum(group_sizes)`` belong to no expert held
here and give 0: the dispatch hands over a buffer at a static row bound of
which only the leading rows are routed to this chip's experts — the tight
bound (``models/moe.tight_rows``, about twice the rows an even router
sends here) while those rows fit in it, else ``T·min(k, E_held)``.

Kernel strategy (MegaBlocks-style tile alignment + scalar prefetch):

  1. Each expert's row segment is padded up to a multiple of the row tile
     ``bm`` inside a scratch layout ``xp`` so that every (bm, K) tile
     belongs to exactly ONE expert (``kernels/ref.py::grouped_layout``,
     shared with the jnp reference).  Live tiles come first; dead rows fill
     the tiles after them.  The static bound on the padded row count is
     ``round_up(N, bm) + min(E, N)·bm``.
  2. Two scalar-prefetch operands: the per-tile expert-id table, so the
     WEIGHT BlockSpec's index map selects each tile's expert block, and the
     count of live tiles.  Grid steps past that count clamp every index map
     to the last live tile — the blocks do not change, so nothing is
     copied — and skip their body: the kernels' time follows the rows
     routed here, not the static bound.
  3. ``grouped_matmul`` (kernel name ``grouped_matmul``): grid (out-column
     tiles × row tiles), row tiles innermost, so an expert's weight block
     is fetched once per run of its tiles; each step one [bm, C] x [C, bn]
     contraction.  The backward's dx is the same kernel on the transposed
     weights (it contracts w's minor axis).
  4. The backward's dw (kernel name ``grouped_matmul_wgrad``) follows
     megablox's ``tgmm``: grid (K tiles × F tiles × row tiles), a VMEM
     accumulator zeroed on an expert's first tile and stored on its last,
     so each expert's [bk, bf] block of dw is written once.  An expert that
     got no row owns no tile and is zeroed outside the kernel.

Both kernels multiply in one bf16 MXU pass with fp32 accumulation: the
operands are cast to bf16 as they are laid out, the precision XLA's default
gives the model's other fp32 matmuls on TPU.  Like every kernel here they
are reached only through ``kernels/ops.py::dispatch`` — "auto" uses them on
TPU and the blocked-scan jnp reference (``ref.grouped_matmul_ref``, fp32,
plain autodiff) everywhere else; off-TPU interpret mode is the explicit
``impl="pallas"`` escape hatch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import _round_up, grouped_layout

MXU_DTYPE = jnp.bfloat16
# the largest weight block a forward step holds (double-buffered), and the
# widest block of dw; with the row tiles these stay well inside the scoped
# VMEM asked for below at Moonlight's widths (2048 x 1408)
W_BLOCK_BYTES = 8 << 20
DW_BLOCK = 2048
VMEM_LIMIT = 64 << 20


def _lane_block(n: int, cap: int) -> int:
    """The widest multiple of 128 that divides ``n`` (itself a multiple of
    128) and is at most ``cap`` (128 at least)."""
    best = 128
    for b in range(128, min(n, max(cap, 128)) + 1, 128):
        if n % b == 0:
            best = b
    return best


def launch_geometry(N: int, K: int, E: int, F: int, *,
                    block_m: int = 128) -> dict:
    """The static launch geometry of one forward grouped_matmul call — the
    single source of truth shared with the compiled-program auditor's R5
    rule (analysis/audit.py) and ``models/moe.share_geometry``: tile sizes,
    padded extents, and the grid, all derivable from shapes alone (``Np``
    is the static grouped_layout bound, independent of the runtime
    group_sizes)."""
    bm = min(block_m, _round_up(max(N, 1), 8))
    Kp = _round_up(K, 128)
    Fp = _round_up(F, 128)
    bn = _lane_block(Fp, W_BLOCK_BYTES // (2 * Kp))
    Np = _round_up(max(N, 1), bm) + min(E, max(N, 1)) * bm
    return {"bm": bm, "bn": bn, "Kp": Kp, "Fp": Fp, "Np": Np,
            "grid": (Fp // bn, Np // bm)}


def _gmm_kernel(tile_eid_ref, n_live_ref, x_ref, w_ref, out_ref, *,
                transpose_w: bool):
    del tile_eid_ref  # consumed by the weight index map

    @pl.when(pl.program_id(1) < n_live_ref[0])
    def _():
        out_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[0],
            dimension_numbers=(((1,), (1 if transpose_w else 0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(out_ref.dtype)


def _gmm(xp, wp, tile_eid, n_live, *, bm: int, bn: int, transpose_w: bool,
         out_dtype, interpret: bool):
    """[Np, C] tiles x wp [E, C, Fo] (or [E, Fo, C]) -> [Np, Fo]."""
    Np, C = xp.shape
    Fo = wp.shape[1] if transpose_w else wp.shape[2]
    last = lambda t, n: jnp.minimum(t, jnp.maximum(n[0] - 1, 0))
    if transpose_w:
        w_spec = pl.BlockSpec((1, bn, C),
                              lambda f, t, eid, n: (eid[last(t, n)], f, 0))
    else:
        w_spec = pl.BlockSpec((1, C, bn),
                              lambda f, t, eid, n: (eid[last(t, n)], 0, f))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_w=transpose_w),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(Fo // bn, Np // bm),
            in_specs=[pl.BlockSpec((bm, C),
                                   lambda f, t, eid, n: (last(t, n), 0)),
                      w_spec],
            out_specs=pl.BlockSpec((bm, bn),
                                   lambda f, t, eid, n: (last(t, n), f)),
        ),
        out_shape=jax.ShapeDtypeStruct((Np, Fo), out_dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="grouped_matmul",
    )(tile_eid, n_live, xp, wp)


def _wgrad_kernel(tile_eid_ref, n_live_ref, x_ref, dy_ref, out_ref, acc_ref):
    t = pl.program_id(2)
    n = n_live_ref[0]
    eid = tile_eid_ref[t]
    first = (t == 0) | (eid != tile_eid_ref[jnp.maximum(t - 1, 0)])
    nxt = tile_eid_ref[jnp.minimum(t + 1, pl.num_programs(2) - 1)]
    last = (t == n - 1) | (eid != nxt)

    @pl.when(t < n)
    def _():
        @pl.when(first)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], dy_ref[...],
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(last)
        def _():
            out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def _wgrad(xp, dyp, tile_eid, n_live, E: int, *, bm: int, interpret: bool):
    """dw[e] = sum over e's tiles of xp_tile^T @ dyp_tile: [E, Kp, Fp]."""
    Np, Kp = xp.shape
    Fp = dyp.shape[1]
    bk, bf = _lane_block(Kp, DW_BLOCK), _lane_block(Fp, DW_BLOCK)
    last = lambda t, n: jnp.minimum(t, jnp.maximum(n[0] - 1, 0))
    return pl.pallas_call(
        _wgrad_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(Kp // bk, Fp // bf, Np // bm),
            in_specs=[pl.BlockSpec((bm, bk),
                                   lambda k, f, t, eid, n: (last(t, n), k)),
                      pl.BlockSpec((bm, bf),
                                   lambda k, f, t, eid, n: (last(t, n), f))],
            out_specs=pl.BlockSpec(
                (1, bk, bf), lambda k, f, t, eid, n: (eid[last(t, n)], k, f)),
            scratch_shapes=[pltpu.VMEM((bk, bf), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((E, Kp, Fp), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="grouped_matmul_wgrad",
    )(tile_eid, n_live, xp, dyp)


def _lay_out(a, dst, Np: int, cols: int):
    """Rows of ``a`` [N, c] at ``dst`` in a zeroed [Np, cols] bf16 buffer."""
    a = jnp.pad(a.astype(MXU_DTYPE), ((0, 0), (0, cols - a.shape[1])))
    return jnp.zeros((Np, cols), MXU_DTYPE).at[dst].set(a)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grouped_matmul(x, w, group_sizes, block_m, interpret):
    return _fwd(x, w, group_sizes, block_m, interpret)[0]


def _pad_w(w, Kp: int, Fp: int):
    return jnp.pad(w.astype(MXU_DTYPE),
                   ((0, 0), (0, Kp - w.shape[1]), (0, Fp - w.shape[2])))


def _fwd(x, w, group_sizes, block_m, interpret):
    N, K = x.shape
    E, _, F = w.shape
    g = launch_geometry(N, K, E, F, block_m=block_m)
    bm, Kp, Fp = g["bm"], g["Kp"], g["Fp"]
    dst, tile_eid, n_live, Np = grouped_layout(group_sizes, N, bm)
    assert Np == g["Np"], (Np, g["Np"])
    n_live = n_live.reshape(1).astype(jnp.int32)
    xp = _lay_out(x, dst, Np, Kp)
    out = _gmm(xp, _pad_w(w, Kp, Fp), tile_eid, n_live, bm=bm, bn=g["bn"],
               transpose_w=False, out_dtype=x.dtype, interpret=interpret)
    live = (jnp.arange(N) < jnp.sum(group_sizes))[:, None]
    out = jnp.where(live, out[dst, :F], 0)
    return out, (xp, w, group_sizes, dst, tile_eid, n_live, live)


def _bwd(block_m, interpret, res, dy):
    xp, w, group_sizes, dst, tile_eid, n_live, live = res
    N, F = dy.shape
    E, K, _ = w.shape
    g = launch_geometry(N, K, E, F, block_m=block_m)
    bm, Kp, Fp = g["bm"], g["Kp"], g["Fp"]
    dyp = _lay_out(dy, dst, xp.shape[0], Fp)
    dx = _gmm(dyp, _pad_w(w, Kp, Fp), tile_eid, n_live, bm=bm,
              bn=_lane_block(Kp, W_BLOCK_BYTES // (2 * Fp)), transpose_w=True,
              out_dtype=dy.dtype, interpret=interpret)
    dx = jnp.where(live, dx[dst, :K], 0)
    dw = _wgrad(xp, dyp, tile_eid, n_live, E, bm=bm, interpret=interpret)
    dw = jnp.where((group_sizes > 0)[:, None, None], dw[:, :K, :F], 0)
    return dx, dw.astype(w.dtype), None


_grouped_matmul.defvjp(_fwd, _bwd)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def grouped_matmul(x, w, group_sizes, *, block_m: int = 128,
                   interpret: bool = False):
    """out[i] = x[i] @ w[g(i)]; x: [N, K] sorted by group, w: [E, K, F],
    group_sizes: [E] with sum <= N (rows past it give 0).  Differentiable in
    x and w through the two backward kernels.  See ref.grouped_matmul_ref."""
    assert x.shape[1] == w.shape[1], (x.shape, w.shape)
    return _grouped_matmul(x, w, group_sizes, block_m, interpret)
