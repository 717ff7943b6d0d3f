"""Fused CoDA proximal local-update Pallas kernel.

    v ← (γ·(v − η·g) + η·v₀) / (η + γ)

Elementwise over the flattened parameter vector, blocked into VMEM tiles.
Fusing keeps the update at 3 HBM reads + 1 write per element (v, g, v₀ → v)
instead of the 5+ round-trips of the unfused expression; η (which changes
every stage) rides in SMEM so the kernel is not re-specialized per stage.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(scal_ref, v_ref, g_ref, v0_ref, out_ref):
    eta = scal_ref[0]
    gamma = scal_ref[1]
    v = v_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    v0 = v0_ref[...].astype(jnp.float32)
    out = (gamma * (v - eta * g) + eta * v0) / (eta + gamma)
    out_ref[...] = out.astype(out_ref.dtype)


def launch_geometry(N: int, *, block: int = 4096, dtypes=(jnp.float32,)) -> dict:
    """Static launch geometry of one flat elementwise update over ``N``
    elements whose operands have ``dtypes``; shared with ``opt_update`` and
    with the auditor's R5 rule (analysis/audit.py).

    Mosaic lays a 1-D block out in 128-lane rows and packs ``4 // itemsize``
    narrow elements per 32-bit word, so a block must span a whole number of
    packed rows: 128 elements for f32, 256 for bf16.  ``bt`` is rounded up
    to that alignment (the tail is padded) and ``block`` is a multiple of it.
    """
    align = 128 * max(4 // jnp.dtype(d).itemsize for d in dtypes)
    bt = min(-(-block // align) * align, -(-max(N, 1) // align) * align)
    n = -(-N // bt)
    return {"bt": bt, "Np": n * bt, "grid": (n,)}


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def prox_update(v, g, v0, eta, gamma, *, block: int = 4096, interpret: bool = False):
    """Flat arrays v, g, v0: [N].  eta may be traced; gamma static-ish scalar."""
    N = v.shape[0]
    geo = launch_geometry(N, block=block, dtypes=(v.dtype, g.dtype, v0.dtype))
    bt, Np = geo["bt"], geo["Np"]
    pad = lambda x: jnp.pad(x, (0, Np - N))
    scal = jnp.stack([jnp.asarray(eta, jnp.float32), jnp.asarray(gamma, jnp.float32)])
    out = pl.pallas_call(
        _kernel,
        grid=geo["grid"],
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bt,), lambda i: (i,)),
            pl.BlockSpec((bt,), lambda i: (i,)),
            pl.BlockSpec((bt,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((bt,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((Np,), v.dtype),
        interpret=interpret,
    )(scal, pad(v), pad(g), pad(v0))
    return out[:N]
