"""Public jit'd wrappers over the Pallas kernels with XLA fallbacks.

``impl`` semantics everywhere (one decision point: ``dispatch``):
  * "auto"   — Pallas on TPU backends; pure-jnp reference on EVERY other
               backend.  In particular a GPU backend gets the XLA-compiled
               reference, never interpret-mode Pallas — interpret mode is a
               correctness tool that runs orders of magnitude slower than
               either a real kernel or the jnp fallback, and "auto" must
               not pick it silently.
  * "ref"    — force the pure-jnp oracle.
  * "pallas" — force the kernel; off-TPU this is the explicit interpret-
               mode override (tests/debugging only).
Anything else raises — a typo'd ``impl`` must not silently fall back.

These wrappers are also what the shard_map CoDA executor
(core/coda_sharded.py) traces inside its manual-mesh region: "auto" never
selects interpret-mode Pallas off-TPU, so the per-worker local steps lower
to plain XLA on forced-host-device CPU meshes and to Mosaic kernels on real
TPU meshes, with no collective ops in either case.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.auc_loss import auc_loss as _auc_kernel
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.moe_dispatch import grouped_matmul as _grouped_kernel
from repro.kernels.opt_update import opt_update as _opt_kernel
from repro.kernels.prox_update import prox_update as _prox_kernel

# Threshold above which the jnp fallback switches from materialized scores to
# the scanned online-softmax form (memory O(S·chunk)).
_FULL_ATTN_MAX_KV = 8192


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def dispatch(impl: str) -> tuple:
    """The one backend-dispatch decision: ``(use_pallas, interpret)``.

    Covered by tests/test_kernels_dispatch.py for every (impl, backend)
    pair — the invariants are that "auto" never returns interpret mode
    (non-TPU backends go to kernels/ref.py instead) and that only the
    explicit "pallas" override may interpret off-TPU.
    """
    if impl == "pallas":
        return True, not _on_tpu()
    if impl == "ref":
        return False, False
    if impl == "auto":
        return _on_tpu(), False
    raise ValueError(f"unknown impl {impl!r} (want auto | ref | pallas)")


def attention(q, k, v, *, causal: bool = True, window=None, impl: str = "auto"):
    """GQA attention.  q: [B,S,H,hd], k: [B,Skv,KV,hd], v: [B,Skv,KV,hv]
    -> [B,S,H,hv].

    ``window``: None / -1 = full; a Python int enables the Pallas kernel's
    block skipping; a traced scalar falls back to masked jnp (used inside
    scanned heterogeneous stacks, e.g. Hymba).  The flash kernel holds one
    head width, so a narrower value head (latent attention) takes the jnp
    path.
    """
    static_window = window is None or isinstance(window, int)
    if static_window and isinstance(window, int) and window < 0:
        window = None
    use_pallas, interpret = dispatch(impl)
    if (use_pallas and (static_window or impl == "pallas")
            and v.shape[-1] == q.shape[-1]):
        return _flash(q, k, v, causal=causal, window=window,
                      interpret=interpret)
    if k.shape[1] <= _FULL_ATTN_MAX_KV:
        return ref.attention_full(q, k, v, causal=causal, window=window)
    return ref.attention_chunked(q, k, v, causal=causal, window=window)


def auc_loss(h, y, a, b, alpha, p, *, impl: str = "auto"):
    """Fused loss + closed-form grads of the min-max AUC objective.

    This is the kernel behind ``objective.auc_F`` (the ``auc`` entry of the
    pluggable objective registry, core/objective.py): one pass over the
    scores yields the forward value and all four partials, wired into
    autodiff via ``custom_vjp``.  New objectives that admit closed-form
    partials should follow the same seam — jnp reference in kernels/ref.py,
    Pallas kernel behind ``dispatch(impl)``.
    """
    use_pallas, interpret = dispatch(impl)
    if use_pallas:
        return _auc_kernel(h, y, a, b, alpha, p, interpret=interpret)
    return ref.auc_loss_ref(h, y, a, b, alpha, p)


def grouped_matmul(x, w, group_sizes, *, impl: str = "auto"):
    """Ragged grouped GEMM: out[i] = x[i] @ w[g(i)] for rows sorted by
    group.  x: [N, K]; w: [E, K, F]; group_sizes: [E] (sum <= N: rows
    past the sum belong to no group and give 0).

    The compute core of the sorted dropless MoE dispatch (models/moe.py):
    "auto" runs the tile-aligned Pallas kernel on TPU and the blocked-scan
    jnp reference everywhere else — never interpret-mode Pallas.
    """
    use_pallas, interpret = dispatch(impl)
    if use_pallas:
        return _grouped_kernel(x, w, group_sizes, interpret=interpret)
    return ref.grouped_matmul_ref(x, w, group_sizes)


def opt_update(v, g, v0, buf, eta, gamma, coef, seed, *, mode: str,
               impl: str = "auto"):
    """Fused optimizer update (the core/optimizer.py seam): accumulator
    update + preconditioned step + prox projection in one pass over a
    parameter leaf, returning ``(new_v, new_buf)``.

    ``mode="momentum"``: buf is the momentum buffer (m ← coef·m + g, d = m;
    bf16 buffers re-stored with stochastic rounding).  ``mode="precond"``:
    buf is the fp32 accumulator cover (ν = cover + g², d = g·rsqrt(ν+coef),
    ν returned fp32 for the caller's axis reductions).  The jnp oracle and
    the kernel share the rounding hash bit-exactly."""
    use_pallas, interpret = dispatch(impl)
    if use_pallas:
        nv, nb = _opt_kernel(v.reshape(-1), g.reshape(-1), v0.reshape(-1),
                             buf.reshape(-1), eta, gamma, coef, seed,
                             mode=mode, interpret=interpret)
        return nv.reshape(v.shape), nb.reshape(buf.shape)
    return ref.opt_update_ref(v, g, v0, buf, eta, gamma, coef, seed,
                              mode=mode)


def prox_update_tree(v_tree, g_tree, v0_tree, eta, gamma, *, impl: str = "auto"):
    """Apply the fused proximal update leaf-wise over parameter pytrees,
    each leaf in its own shape (the kernel picks its launch from the rank)."""
    use_pallas, interpret = dispatch(impl)

    def upd(v, g, v0):
        if use_pallas:
            return _prox_kernel(v, g, v0, eta, gamma, interpret=interpret)
        return ref.prox_update_ref(v, g, v0, eta, gamma)

    return jax.tree_util.tree_map(upd, v_tree, g_tree, v0_tree)
