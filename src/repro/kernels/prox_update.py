"""Fused CoDA proximal local-update Pallas kernel.

    v ← (γ·(v − η·g) + η·v₀) / (η + γ)

Elementwise over one parameter leaf, blocked into VMEM tiles.  Fusing keeps
the update at 3 HBM reads + 1 write per element (v, g, v₀ → v) instead of
the 5+ round-trips of the unfused expression; η (which changes every stage)
rides in SMEM so the kernel is not re-specialized per stage.

Two launches, chosen from the leaf's rank alone:

  * rank ≥ 2 (``tiled_geometry``): the leaf's own shape.  A TPU keeps such
    an array in HBM as (8·packing, 128) tiles of its two minor axes, so the
    kernel blocks those two axes as (rows, lanes) tiles of about
    ``BLOCK_BYTES`` per operand and puts the leading axes into the grid:
    collapsed into the rows where the second-minor axis is a whole number
    of tiles (the collapse is then a bitcast), else one squeezed grid axis
    each.  No flatten, pad or slice surrounds the call, so XLA emits no
    relayout copy of the leaf.
  * rank 0 or 1 (``launch_geometry``): the flat vector in 1-D blocks of
    ``block`` elements, the tail padded.

Both run the same body, so they give the same bits for every element, and
both write the result over v's buffer, so a loop that carries v (the
window's local steps) keeps no second copy of it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# bytes of one operand's block in the tiled launch (the block of each of the
# four operands is double-buffered in VMEM)
BLOCK_BYTES = 1 << 20


def _kernel(scal_ref, v_ref, g_ref, v0_ref, out_ref):
    eta = scal_ref[0]
    gamma = scal_ref[1]
    v = v_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    v0 = v0_ref[...].astype(jnp.float32)
    out = (gamma * (v - eta * g) + eta * v0) / (eta + gamma)
    out_ref[...] = out.astype(out_ref.dtype)


def _packing(dtypes) -> int:
    """Narrow elements per 32-bit word of the narrowest operand."""
    return max(4 // jnp.dtype(d).itemsize for d in dtypes)


def launch_geometry(N: int, *, block: int = 4096, dtypes=(jnp.float32,)) -> dict:
    """Static launch geometry of one flat elementwise update over ``N``
    elements whose operands have ``dtypes``; shared with ``opt_update`` and
    with the auditor's R5 rule (analysis/audit.py).

    Mosaic lays a 1-D block out in 128-lane rows and packs ``4 // itemsize``
    narrow elements per 32-bit word, so a block must span a whole number of
    packed rows: 128 elements for f32, 256 for bf16.  ``bt`` is rounded up
    to that alignment (the tail is padded) and ``block`` is a multiple of it.
    """
    align = 128 * _packing(dtypes)
    bt = min(-(-block // align) * align, -(-max(N, 1) // align) * align)
    n = -(-N // bt)
    return {"bt": bt, "Np": n * bt, "grid": (n,)}


def tiled_geometry(shape, dtypes=(jnp.float32,)) -> dict | None:
    """Static launch geometry of the update on a leaf of ``shape`` in its
    own tiled layout, or None for rank < 2 (the flat launch).

    ``view`` is the shape the kernel sees: the leaf's, or its leading axes
    collapsed into the rows where the second-minor axis is a multiple of
    ``sublanes`` (8 x the narrowest operand's packing).  ``block`` squeezes
    every leading axis of the view and tiles the two minor axes as
    (``rows``, ``lanes``): ``lanes`` a multiple of 128 or the whole axis,
    ``rows`` a multiple of ``sublanes`` or the whole axis, about
    ``BLOCK_BYTES`` at the widest operand's itemsize.  Edge blocks may be
    partial.  ``vmem_bytes`` is the double-buffered blocks of three inputs
    and the output, padded to whole tiles.
    """
    if len(shape) < 2:
        return None
    sub = 8 * _packing(dtypes)
    item = max(jnp.dtype(d).itemsize for d in dtypes)
    *lead, R, C = shape
    view = (math.prod(lead) * R, C) if lead and R % sub == 0 else tuple(shape)
    rows = view[-2]
    pad = lambda n, m: -(-n // m) * m
    # lanes wide enough that a block of at most 32 tiles' rows holds
    # BLOCK_BYTES; then as many rows as the lanes leave room for
    lanes = max(128, BLOCK_BYTES // (min(pad(rows, sub), 32 * sub) * item)
                // 128 * 128)
    lanes = min(lanes, C)
    br = max(sub, BLOCK_BYTES // (pad(lanes, 128) * item) // sub * sub)
    br = min(br, rows)
    return {"view": view, "sublanes": sub, "rows": br, "lanes": lanes,
            "block": (None,) * (len(view) - 2) + (br, lanes),
            "grid": tuple(view[:-2]) + (-(-rows // br), -(-C // lanes)),
            "vmem_bytes": 2 * 4 * pad(br, sub) * pad(lanes, 128) * item}


def tiled_share(tree) -> float:
    """Share of the tree's parameter elements whose update takes the tiled
    launch.  Leaves need only ``shape`` and ``dtype``, so a tree of
    ``jax.ShapeDtypeStruct`` will do."""
    leaves = jax.tree_util.tree_leaves(tree)
    n = [math.prod(x.shape) for x in leaves]
    tiled = sum(k for k, x in zip(n, leaves)
                if tiled_geometry(x.shape, (x.dtype,)) is not None)
    return tiled / sum(n) if sum(n) else 0.0


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def prox_update(v, g, v0, eta, gamma, *, block: int = 4096,
                interpret: bool = False):
    """One leaf v, g, v0 of any shape; eta may be traced, gamma static-ish.
    Rank ≥ 2 runs on the leaf's own layout in blocks of about
    ``BLOCK_BYTES``, rank 0 or 1 flat in blocks of ``block`` elements."""
    dtypes = (v.dtype, g.dtype, v0.dtype)
    scal = jnp.stack([jnp.asarray(eta, jnp.float32),
                      jnp.asarray(gamma, jnp.float32)])
    geo = tiled_geometry(v.shape, dtypes)
    if geo is None:
        flat = launch_geometry(v.size, block=block, dtypes=dtypes)
        grid, view = flat["grid"], (flat["Np"],)
        spec = pl.BlockSpec((flat["bt"],), lambda i: (i,))
        args = [jnp.pad(x.reshape(-1), (0, flat["Np"] - v.size))
                for x in (v, g, v0)]
    else:
        grid, view = geo["grid"], geo["view"]
        spec = pl.BlockSpec(geo["block"], lambda *i: i)
        args = [x.reshape(view) for x in (v, g, v0)]
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(view, v.dtype),
        input_output_aliases={1: 0},
        interpret=interpret,
        name="prox_update",
    )(scal, *args)
    if geo is None:
        out = out[:v.size]
    return out.reshape(v.shape)
