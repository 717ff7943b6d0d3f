"""Fused AUC min-max loss Pallas kernel.

One pass over the score vector produces the loss and all four gradient
components of the paper's objective F(w,a,b,α;z) (eq. 2):

    F = (1-p)(h-a)² 1[y=1] + p(h-b)² 1[y=-1]
        + 2(1+α)(p·h·1[y=-1] - (1-p)·h·1[y=1]) - p(1-p)α²

The batch axis is blocked into VMEM tiles; per-block partial reductions for
(loss, da, db, dα) land in lanes 0..3 of one (8, 128) output tile per block
(Mosaic stores whole vector tiles, not scalars, to VMEM) that the wrapper
sums —
one HBM read of ``h``/``y`` instead of the ~8 masked reductions XLA would
otherwise issue.  Scalar state (a, b, α, p) rides in SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(scal_ref, h_ref, y_ref, dh_ref, parts_ref, *, bt: int, T: int):
    i = pl.program_id(0)
    a, b, alpha, p = (scal_ref[0, 0], scal_ref[0, 1], scal_ref[0, 2],
                      scal_ref[0, 3])
    h = h_ref[...].astype(jnp.float32)
    pos = y_ref[...].astype(jnp.float32)
    neg = 1.0 - pos
    # mask padding rows (the padded tail of the last block)
    row = i * bt + jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1)
    live = (row < T).astype(jnp.float32)
    pos, neg = pos * live, neg * live

    da_h = h - a
    db_h = h - b
    f = ((1 - p) * da_h * da_h * pos + p * db_h * db_h * neg
         + 2 * (1 + alpha) * (p * h * neg - (1 - p) * h * pos)
         - p * (1 - p) * alpha * alpha * live)
    dh = (2 * (1 - p) * da_h * pos + 2 * p * db_h * neg
          + 2 * (1 + alpha) * (p * neg - (1 - p) * pos))
    dh_ref[...] = (dh / T).astype(dh_ref.dtype)
    sums = (jnp.sum(f) / T,
            jnp.sum(-2 * (1 - p) * da_h * pos) / T,
            jnp.sum(-2 * p * db_h * neg) / T,
            (jnp.sum(2 * (p * h * neg - (1 - p) * h * pos)) / T
             - 2 * p * (1 - p) * alpha * jnp.sum(live) / T))
    # Mosaic stores only whole vector tiles to VMEM: partial k goes to lane
    # k of this block's (8, 128) tile, every other lane is zero
    lane = jax.lax.broadcasted_iota(jnp.int32, _PART_TILE, 1)
    tile = jnp.zeros(_PART_TILE, jnp.float32)
    for k, s in enumerate(sums):
        tile = jnp.where(lane == k, s, tile)
    parts_ref[...] = tile


# one (sublane, lane) f32 vreg tile of per-block partials
_PART_TILE = (8, 128)


def launch_geometry(T: int, *, block: int = 1024) -> dict:
    """Static launch geometry of one auc_loss call, shared with the
    auditor's R5 rule (analysis/audit.py).  ``bt`` is rounded up to a
    multiple of the 128-lane width (Mosaic's rule for the lane dim of a
    block), so a short or ragged batch is padded and the kernel masks the
    tail rows."""
    up = lambda x: -(-max(x, 1) // 128) * 128
    bt = min(up(block), up(T))
    n = -(-T // bt)
    return {"bt": bt, "Tp": n * bt, "grid": (n,)}


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def auc_loss(h, y, a, b, alpha, p, *, block: int = 1024, interpret: bool = False):
    """Returns (loss, dh [T], da, db, dalpha) — see ref.auc_loss_ref."""
    T = h.shape[0]
    g = launch_geometry(T, block=block)
    bt, Tp = g["bt"], g["Tp"]
    (n,) = g["grid"]
    hp = jnp.pad(h.astype(jnp.float32), (0, Tp - T))[None]
    yp = jnp.pad(y.astype(jnp.float32), (0, Tp - T))[None]
    # Every operand has a leading axis of 1 ([1, Tp], [1, 4]): under vmap
    # (one call per CoDA worker) a batched block becomes (squeezed, 1, ...),
    # whose last two dims Mosaic accepts; (squeezed, bt) it would refuse
    scal = jnp.stack([jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32),
                      jnp.asarray(alpha, jnp.float32),
                      jnp.asarray(p, jnp.float32)])[None]

    kern = functools.partial(_kernel, bt=bt, T=T)
    dh, parts = pl.pallas_call(
        kern,
        grid=g["grid"],
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bt), lambda i: (0, i)),
            pl.BlockSpec((1, bt), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, bt), lambda i: (0, i)),
            pl.BlockSpec(_PART_TILE, lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, Tp), jnp.float32),
            jax.ShapeDtypeStruct((n * _PART_TILE[0], _PART_TILE[1]),
                                 jnp.float32),
        ],
        interpret=interpret,
    )(scal, hp, yp)
    # row 0 of each block's tile holds its partials in lanes 0..3
    tot = parts.reshape(n, *_PART_TILE)[:, 0, :4].sum(axis=0)
    loss, da, db, dalpha = tot[0], tot[1], tot[2], tot[3]
    return loss, dh[0, :T], da, db, dalpha
