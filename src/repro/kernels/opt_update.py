"""Fused local-optimizer update (Pallas TPU): accumulator update +
preconditioned step + prox projection in ONE streaming pass.

Extends ``prox_update``'s 3-read/1-write discipline to the stateful
optimizers of ``core/optimizer.py``: the kernel reads (v, g, v0, buf) and
writes (v', buf') — 4 reads / 2 writes per element instead of the 8/3 a
separate accumulator-update + precondition + prox sequence would stream
through HBM.  All arithmetic is fp32 in-kernel regardless of the storage
dtypes; bf16 buffers are re-stored with hash-based stochastic rounding
(``kernels/ref.stochastic_round`` — the identical elementwise integer ops
run here and in the jnp oracle, so given the same accumulator bits the two
paths round identically; end-to-end the paths are separately compiled
programs whose FMA contraction may differ, pinned at fp32 noise scale in
tests).

Modes (static):
  * "momentum": buf is the momentum buffer; m = coef·m + g, d = m.
  * "precond":  buf is the fp32 accumulator cover (SM3's min-of-covers);
                ν = cover + g², d = g·rsqrt(ν + coef), ν returned fp32.

Both end with the proximal projection v' = (γ(v − η d) + η v₀)/(η + γ).

Scalars (η, γ, coef) ride SMEM so a schedule's changing η never
re-specializes the kernel; the uint32 stochastic-rounding seed rides its own
SMEM lane (it must not round-trip through f32).  Geometry is
``prox_update``'s ``launch_geometry``: flat 1-D layout, ``block``-wide
tiles aligned for the narrowest operand dtype, audited by rule R5.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref
from repro.kernels.prox_update import launch_geometry


def _kernel(mode, scal_ref, seed_ref, v_ref, g_ref, v0_ref, buf_ref,
            out_ref, buf_out_ref):
    eta = scal_ref[0]
    gamma = scal_ref[1]
    coef = scal_ref[2]
    v = v_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    v0 = v0_ref[...].astype(jnp.float32)
    buf = buf_ref[...].astype(jnp.float32)
    if mode == "momentum":
        acc = coef * buf + g
        d = acc
        new_buf = ref.stochastic_round(acc, seed_ref[0], buf_out_ref.dtype)
    else:  # "precond"
        acc = buf + g * g
        d = g * jax.lax.rsqrt(acc + coef)
        new_buf = acc.astype(buf_out_ref.dtype)
    out = (gamma * (v - eta * d) + eta * v0) / (eta + gamma)
    out_ref[...] = out.astype(out_ref.dtype)
    buf_out_ref[...] = new_buf


@functools.partial(jax.jit, static_argnames=("mode", "block", "interpret"))
def opt_update(v, g, v0, buf, eta, gamma, coef, seed, *, mode: str,
               block: int = 4096, interpret: bool = False):
    """Flat [N] fused optimizer update; returns (new_v [N], new_buf [N])."""
    if mode not in ("momentum", "precond"):
        raise ValueError(f"unknown opt_update mode {mode!r}")
    N = v.shape[0]
    geo = launch_geometry(N, block=block,
                          dtypes=(v.dtype, g.dtype, v0.dtype, buf.dtype))
    bt, Np = geo["bt"], geo["Np"]
    pad = lambda x: jnp.pad(x, (0, Np - N))
    scal = jnp.stack([jnp.asarray(eta, jnp.float32),
                      jnp.asarray(gamma, jnp.float32),
                      jnp.asarray(coef, jnp.float32)])
    seed = jnp.asarray(seed, jnp.uint32).reshape(1)
    tile = lambda: pl.BlockSpec((bt,), lambda i: (i,))
    out_v, out_buf = pl.pallas_call(
        functools.partial(_kernel, mode),
        grid=geo["grid"],
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM),
                  tile(), tile(), tile(), tile()],
        out_specs=(tile(), tile()),
        out_shape=(jax.ShapeDtypeStruct((Np,), v.dtype),
                   jax.ShapeDtypeStruct((Np,), buf.dtype)),
        interpret=interpret)(scal, seed, pad(v), pad(g), pad(v0), pad(buf))
    return out_v[:N], out_buf[:N]
