"""Pure-jnp oracles for every Pallas kernel, plus the XLA fallbacks the
models use on CPU.  These define the semantics the kernels are tested
against.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# --------------------------------------------------------------------------
# attention (GQA, causal / sliding window; `window` may be a traced scalar,
# -1 or None meaning full attention)
# --------------------------------------------------------------------------
def _mask(q_pos, kv_pos, causal: bool, window):
    valid = jnp.ones((q_pos.shape[0], kv_pos.shape[0]), bool)
    if causal:
        valid &= kv_pos[None, :] <= q_pos[:, None]
    if window is not None:
        w = jnp.asarray(window, jnp.int32)
        in_win = kv_pos[None, :] > (q_pos[:, None] - w)
        valid &= jnp.where(w < 0, True, in_win)
    return valid


def attention_full(q, k, v, *, causal=True, window=None):
    """q/k: [B, S(kv), H(KV), hd]; v: [B, Skv, KV, hv] -> [B, S, H, hv].
    Scores are scaled by hd ** -0.5; the value head may be narrower than
    the query/key head (latent attention: 128 against 192)."""
    B, S, H, hd = q.shape
    hv = v.shape[-1]
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, S, KV, G, hd).astype(jnp.float32) * hd ** -0.5
    s = jnp.einsum("bskgh,bckh->bskgc", qg, k.astype(jnp.float32))
    valid = _mask(jnp.arange(S), jnp.arange(k.shape[1]), causal, window)
    s = jnp.where(valid[None, :, None, None, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bskgc,bckh->bskgh", w, v.astype(jnp.float32))
    return o.reshape(B, S, H, hv).astype(q.dtype)


def attention_chunked(q, k, v, *, causal=True, window=None, chunk=512):
    """Online-softmax attention, scanned over KV chunks (O(S·chunk) scores).

    Used for the long prefill shapes where materializing [S, Skv] scores is
    infeasible.  Matches ``attention_full`` to numerical tolerance.
    """
    from repro import flags
    B, S, H, hd = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    hv = v.shape[-1]
    G = H // KV
    C = min(flags.attn_chunk(Skv, chunk), Skv)
    assert Skv % C == 0, (Skv, C)
    qg = q.reshape(B, S, KV, G, hd).astype(jnp.float32) * hd ** -0.5
    kc = jnp.moveaxis(k.reshape(B, Skv // C, C, KV, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, Skv // C, C, KV, hv), 1, 0)
    q_pos = jnp.arange(S)

    def step(carry, inp):
        m, l, acc = carry
        j, kj, vj = inp
        s = jnp.einsum("bskgh,bckh->bskgc", qg, kj.astype(jnp.float32))
        kv_pos = j * C + jnp.arange(C)
        valid = _mask(q_pos, kv_pos, causal, window)
        s = jnp.where(valid[None, :, None, None, :], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("bskgc,bckh->bskgh", p,
                                                 vj.astype(jnp.float32))
        return (m_new, l, acc), None

    m0 = jnp.full((B, S, KV, G), -1e30, jnp.float32)
    l0 = jnp.zeros((B, S, KV, G), jnp.float32)
    a0 = jnp.zeros((B, S, KV, G, hv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0), (jnp.arange(Skv // C), kc, vc),
        unroll=flags.scan_unroll())
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    return o.reshape(B, S, H, hv).astype(q.dtype)


# --------------------------------------------------------------------------
# AUC min-max objective (Ying et al. 2016 reformulation) — fused loss+grads
# --------------------------------------------------------------------------
def auc_loss_ref(h, y, a, b, alpha, p):
    """Per-batch mean of F(w,a,b,α;z) and its closed-form partials.

    h: scores [T] ∈ [0,1]; y: labels [T] ∈ {0,1} (1 = positive);
    a, b, alpha, p: scalars.  Returns (loss, dh [T], da, db, dalpha).
    """
    h = h.astype(jnp.float32)
    pos = y.astype(jnp.float32)
    neg = 1.0 - pos
    T = h.shape[0]
    f = ((1 - p) * (h - a) ** 2 * pos
         + p * (h - b) ** 2 * neg
         + 2 * (1 + alpha) * (p * h * neg - (1 - p) * h * pos)
         - p * (1 - p) * alpha ** 2)
    loss = jnp.mean(f)
    dh = (2 * (1 - p) * (h - a) * pos + 2 * p * (h - b) * neg
          + 2 * (1 + alpha) * (p * neg - (1 - p) * pos)) / T
    da = jnp.sum(-2 * (1 - p) * (h - a) * pos) / T
    db = jnp.sum(-2 * p * (h - b) * neg) / T
    dalpha = jnp.sum(2 * (p * h * neg - (1 - p) * h * pos)) / T - 2 * p * (1 - p) * alpha
    return loss, dh, da, db, dalpha


# --------------------------------------------------------------------------
# ragged grouped GEMM (sort-based dropless MoE dispatch)
# --------------------------------------------------------------------------
def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def grouped_layout(group_sizes, n_rows: int, block_rows: int):
    """Row mapping for a tile-aligned grouped layout.

    Pads each group's row segment up to a multiple of ``block_rows`` so
    every row tile belongs to exactly ONE group.  Rows past
    ``sum(group_sizes)`` are dead (routed to no group here): they land in
    the tiles after the last live one.  Returns ``(dst [N], tile_gid
    [n_tiles], n_live_tiles, n_padded)``: sorted row i lands at ``dst[i]``
    in the padded buffer, tile t is owned by group ``tile_gid[t]``, and
    tiles ``[0, n_live_tiles)`` hold every live row.  ``n_padded`` is the
    static bound ``round_up(N, bm) + min(E, N)·bm`` — at most one tile of
    slack per NON-EMPTY group (at most min(E, N) of those), negligible next
    to the capacity path's E/top_k-fold padding.  Shared by the jnp
    reference below and the Pallas kernels (kernels/moe_dispatch.py).
    """
    E = group_sizes.shape[0]
    gs = group_sizes.astype(jnp.int32)
    inc = jnp.cumsum(gs)
    exc = inc - gs
    pc = ((gs + block_rows - 1) // block_rows) * block_rows
    pinc = jnp.cumsum(pc)
    pexc = pinc - pc
    rows = jnp.arange(n_rows, dtype=jnp.int32)
    g_row = jnp.searchsorted(inc, rows, side="right")
    dead = g_row >= E
    g_row = jnp.minimum(g_row, E - 1)
    dst = jnp.where(dead, pinc[-1] + rows - inc[-1],
                    pexc[g_row] + (rows - exc[g_row]))
    n_padded = (_round_up(max(n_rows, 1), block_rows)
                + min(E, max(n_rows, 1)) * block_rows)
    tile_starts = jnp.arange(n_padded // block_rows,
                             dtype=jnp.int32) * block_rows
    tile_gid = jnp.clip(jnp.searchsorted(pinc, tile_starts, side="right"),
                        0, E - 1).astype(jnp.int32)
    return dst, tile_gid, pinc[-1] // block_rows, n_padded


def grouped_matmul_ref(x, w, group_sizes, *, block_rows: int = 128):
    """out[i] = x[i] @ w[g(i)] for rows of ``x`` sorted by group id.

    x: [N, K] with the first ``group_sizes[0]`` rows belonging to group 0,
    the next ``group_sizes[1]`` to group 1, ...; w: [E, K, F];
    group_sizes: [E] int with ``sum <= N``: rows past the sum belong to no
    group and give 0.  Returns [N, F].

    NOT ``lax.ragged_dot``: on jax 0.4.x that primitive's only lowering is
    ragged_to_dense — it materializes a masked [E, N, K] operand, i.e.
    exactly the E-fold blow-up the sorted dispatch exists to remove.  This
    oracle instead scans over tile-aligned row blocks (``grouped_layout``),
    dynamically gathering ONE group's [K, F] weight block per tile:
    O(N·K·F) FLOPs, O(N·K + K·F) live memory, differentiable w.r.t. ``x``
    and ``w``, and vmappable with shared or stacked weights.
    """
    N, K = x.shape
    E, _, F = w.shape
    bm = min(block_rows, _round_up(max(N, 1), 8))
    dst, tile_gid, _, Np = grouped_layout(group_sizes, N, bm)
    xb = jnp.zeros((Np, K), x.dtype).at[dst].set(x).reshape(-1, bm, K)

    def body(_, inp):
        xt, g = inp
        return None, xt @ jax.lax.dynamic_index_in_dim(w, g, keepdims=False)

    _, yb = jax.lax.scan(body, None, (xb, tile_gid))
    live = jnp.arange(N) < jnp.sum(group_sizes)
    return jnp.where(live[:, None], yb.reshape(Np, F)[dst], 0)


# --------------------------------------------------------------------------
# CoDA fused proximal local update
# --------------------------------------------------------------------------
def prox_update_ref(v, g, v0, eta, gamma):
    """v ← argmin_u g·u + ‖u−v‖²/(2η) + ‖u−v0‖²/(2γ)
         = (γ(v − ηg) + ηv0) / (η + γ)."""
    eta = jnp.asarray(eta, jnp.float32)
    vf = v.astype(jnp.float32)
    out = (gamma * (vf - eta * g.astype(jnp.float32)) + eta * v0.astype(jnp.float32))
    return (out / (eta + gamma)).astype(v.dtype)


# --------------------------------------------------------------------------
# fused optimizer update (core/optimizer.py seam)
# --------------------------------------------------------------------------
def _mix_bits(x):
    """uint32 avalanche hash (xorshift-multiply finalizer).  Elementwise
    integer ops only, so the SAME function runs inside the Pallas kernel
    and in this oracle — the two paths round bit-identically."""
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> jnp.uint32(15))
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> jnp.uint32(16))
    return x


def stochastic_round(x, seed, dtype):
    """fp32 → ``dtype`` with deterministic hash-based stochastic rounding.

    ``dtype=float32`` is the identity (no rounding is traced).  For bf16 the
    random low-16 bits come from hashing the value's own bit pattern with a
    per-(step, leaf) uint32 ``seed``: deterministic given (value, seed), so
    checkpoint resume replays bitwise, with no PRNG key threaded through the
    local steps.  Rounding is add-low-bits-then-truncate: unbiased, and the
    expected value of the stored buffer equals the fp32 master value."""
    if jnp.dtype(dtype) == jnp.dtype(jnp.float32):
        return x.astype(jnp.float32)
    assert jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16), dtype
    xi = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    r = _mix_bits(xi ^ seed) & jnp.uint32(0x0000FFFF)
    yi = (xi + r) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(yi, jnp.float32).astype(jnp.bfloat16)


def opt_update_ref(v, g, v0, buf, eta, gamma, coef, seed, *, mode):
    """Oracle for the fused optimizer update (kernels/opt_update.py):
    accumulator update + preconditioned step + prox projection in one pass.

    mode="momentum": ``buf`` is the momentum buffer (fp32 or bf16);
        m = coef·m + g, d = m, new buffer stochastically rounded to
        ``buf.dtype``.  coef = 0 reproduces ``prox_update_ref`` bitwise.
    mode="precond": ``buf`` is the fp32 accumulator cover (e.g. SM3's
        min-of-covers); ν = cover + g², d = g·rsqrt(ν + coef), and ν comes
        back fp32 for the caller's axis reductions.
    Returns (new_v, new_buf)."""
    eta = jnp.asarray(eta, jnp.float32)
    coef = jnp.asarray(coef, jnp.float32)
    vf = v.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    bf = buf.astype(jnp.float32)
    if mode == "momentum":
        acc = coef * bf + gf
        d = acc
        new_buf = stochastic_round(acc, seed, buf.dtype)
    elif mode == "precond":
        acc = bf + gf * gf
        d = gf * jax.lax.rsqrt(acc + coef)
        new_buf = acc
    else:
        raise ValueError(f"unknown opt_update mode {mode!r}")
    out = (gamma * (vf - eta * d) + eta * v0.astype(jnp.float32))
    return (out / (eta + gamma)).astype(v.dtype), new_buf
