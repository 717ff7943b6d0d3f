"""Mixture-of-experts block with top-k routing and two dispatch modes.

Routing is shared (``route``): top-k over a softmax router (Switch
auxiliary loss), or DeepSeek-V3's sigmoid router with a selection bias
(``MoEConfig.scoring``); gate weights renormalized over the chosen k and
scaled by ``routed_scale``.  What differs is how chosen tokens reach their
experts:

* ``capacity`` — padded scatter dispatch into an ``[E, C, d]`` buffer with
  ``C = ceil(T · top_k · capacity_factor / E)`` at train time (over-capacity
  tokens are DROPPED — the standard load-shedding regularizer, and what
  keeps expert FLOPs at the *active* count) or the static dropless bound
  ``C = T`` at eval.  The expert axis is the sharding target for expert
  parallelism (see sharding/rules.py); GSPMD turns the gather/scatter across
  a sharded expert axis into all-to-all style collectives.

* ``sorted`` — dropless sort-based dispatch: the flat ``[T·k]`` (token,
  expert) assignments are argsorted by held expert id (assignments to
  experts not held here sort last), per-expert segment sizes come from a
  bincount, the expert MLP runs as a ragged grouped GEMM over the leading
  rows of the sorted buffer (``kernels/ops.py::grouped_matmul`` — a
  blocked-scan jnp reference on CPU/GPU, tile-aligned scalar-prefetch
  Pallas kernels with a custom VJP on TPU), and a segment-aware
  scatter-add combines the results.  How many leading rows is a static
  bound: ``T·min(k, E_held)`` (``held_rows``) can never be exceeded, but a
  held share routes about ``T·k·E_held/E`` rows, so where ``tight_rows``
  (``ROW_SLACK`` times that) is tighter, the layer runs at it whenever the
  routed rows fit and at the full bound otherwise — the same numbers
  either way.  No ``E``-fold padding: at ``C = T`` the capacity buffer is
  ``E/top_k``-fold oversized in expectation (64× on arctic-480b).

Training and eval/decode alike use ``cfg.moe.dispatch`` (default
``"sorted"``, dropless, as DeepSeek-V3 trains); ``"capacity"`` keeps the
capacity-factor path for training and the dropless C = T path at eval.
Both eval modes see bitwise-identical routing decisions — only the
dispatch plumbing differs (``benchmarks/run.py --only moe_dispatch``
measures the wall-clock and buffer-bytes gap).

The held share (``MoEConfig.held_offset``/``n_held``) is expert
parallelism without its exchange: the layer allocates only its own
experts, routes every token over all of them, and returns its experts'
part of the result; what the absent experts add is left out.
``dense_residual`` adds an always-on, unscaled MLP of its own width beside
the experts: Arctic's dense residual, or DeepSeek's shared experts as one
SwiGLU of their summed width.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

from repro import tracing
from repro.configs.base import ModelConfig
from repro.kernels import moe_dispatch, ops
from repro.models.mlp import apply_mlp, init_mlp

# The tight row bound's slack over the rows an even router sends to the
# held experts (T·k·E_held/E): Moonlight's share routed at most 1.2x that
# to one layer on a measured batch, and a batch that routes more than the
# slack runs at the full bound, so the slack costs time, never results.
ROW_SLACK = 2


def _constrain(x, spec):
    """Optional explicit sharding on MoE intermediates (§Perf knob)."""
    from repro import flags
    if not flags.MOE_SHARDING_CONSTRAINTS:
        return x
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(x, P(*spec))


def init_moe(key, cfg: ModelConfig, dtype=jnp.float32):
    m = cfg.moe
    d, ff, E = cfg.d_model, cfg.d_ff, m.n_held
    kr, k1, k2, k3, kd = jax.random.split(key, 5)
    s = d ** -0.5
    p = {
        "router": jax.random.normal(kr, (d, m.n_experts), jnp.float32) * s,
        "w_gate": jax.random.normal(k1, (E, d, ff), dtype) * s,
        "w_up": jax.random.normal(k2, (E, d, ff), dtype) * s,
        "w_down": jax.random.normal(k3, (E, ff, d), dtype) * ff ** -0.5,
    }
    if m.scoring == "sigmoid":
        p["select_bias"] = jnp.zeros((m.n_experts,), jnp.float32)
    if m.dense_residual:
        p["dense"] = init_mlp(kd, cfg, d_ff=m.dense_d_ff, dtype=dtype)
    return p


def held_rows(cfg: ModelConfig, n_tokens: int) -> int:
    """Static bound on the rows routed to the held experts: each token
    reaches at most min(k, E_held) of them."""
    m = cfg.moe
    return n_tokens * min(m.top_k, m.n_held)


def tight_rows(cfg: ModelConfig, n_tokens: int) -> int:
    """The row bound the sorted dispatch runs at while the routed rows fit:
    ``ROW_SLACK`` times the rows an even router sends to the held experts,
    rounded up to the grouped GEMM's row tile, or ``held_rows`` where that
    is no tighter (every expert held, or few tokens)."""
    m = cfg.moe
    rows = -(-ROW_SLACK * n_tokens * m.top_k * m.n_held // m.n_experts)
    bm = moe_dispatch.launch_geometry(rows, cfg.d_model, m.n_held,
                                      cfg.d_ff)["bm"]
    return min(held_rows(cfg, n_tokens), -(-rows // bm) * bm)


def share_geometry(cfg: ModelConfig, n_tokens: int) -> dict:
    """What the sorted dispatch of one MoE layer launches for ``n_tokens``,
    from shapes alone: the static row bound, the tight bound it runs at
    while the routed rows fit (``tight_rows``), the rows expected live when
    the router spreads tokens evenly (T·k·E_held/E), and the forward grouped
    GEMM's tiles and grid at the static bound (``grid[1]`` row tiles, of
    which about ``expected_rows / bm + E_held`` are live)."""
    m = cfg.moe
    R = held_rows(cfg, n_tokens)
    g = moe_dispatch.launch_geometry(R, cfg.d_model, m.n_held, cfg.d_ff)
    return {"row_bound": R, "tight_rows": tight_rows(cfg, n_tokens),
            "expected_rows": n_tokens * m.top_k * m.n_held / m.n_experts,
            "bm": g["bm"], "padded_rows": g["Np"], "grid": g["grid"]}


def capacity(cfg: ModelConfig, n_tokens: int, *, train: bool = True) -> int:
    """Per-expert buffer slots for ``capacity`` dispatch: the capacity-factor
    bound at train time, the static dropless bound C = T at eval (dropping
    depends on the token count of the forward pass, so a capacity-limited
    parallel scoring pass and a token-by-token decode would route the same
    sequence differently — tests/test_decode_consistency.py caught exactly
    that divergence on dbrx's top-2-of-4 router)."""
    m = cfg.moe
    c = math.ceil(n_tokens * m.top_k * m.capacity_factor / m.n_experts) \
        if train else n_tokens
    return max(4, c + (-c) % 4)  # pad to a multiple of 4


def dispatch_buffer_bytes(cfg: ModelConfig, n_tokens: int, *,
                          mode: str = "sorted", train: bool = False,
                          dtype=jnp.float32) -> int:
    """Bytes of the per-layer dispatch buffer a forward pass of ``n_tokens``
    allocates under each mode — the quantity the moe_dispatch benchmark and
    scripts/mem_pass.py account for.  ``sorted`` gathers at most [T·min(k,
    E_held), d] (``held_rows``; [``tight_rows``, d] while the routed rows
    fit); ``capacity`` gathers [E, C, d]."""
    m = cfg.moe
    itemsize = jnp.dtype(dtype).itemsize
    if mode == "sorted":
        return held_rows(cfg, n_tokens) * cfg.d_model * itemsize
    if mode == "capacity":
        return (m.n_experts * capacity(cfg, n_tokens, train=train)
                * cfg.d_model * itemsize)
    raise ValueError(f"unknown dispatch mode {mode!r}")


def tokens_per_forward(spec) -> int:
    """Tokens one forward pass dispatches for a benchmark shape spec
    (configs.SHAPES): the full batch for train/prefill, one token per
    sequence for decode.  The single convention behind the moe_dispatch
    benchmark and scripts/mem_pass.py's artifact stamping."""
    return (spec.global_batch if spec.kind == "decode"
            else spec.global_batch * spec.seq_len)


def route(cfg: ModelConfig, p, xf):
    """Shared routing decision.  xf: [T, d] -> (top_g [T, k] fp32 gate
    weights, top_e [T, k] int32 expert ids over all E, gates [T, E] fp32).
    Both dispatch modes consume exactly this — the modes are
    bitwise-identical in WHAT they route and differ only in how tokens
    reach the experts.

    softmax: top-k of the softmax.  sigmoid (``noaux_tc``, one group):
    scores s = sigmoid(x W_r) in fp32, top-k of s + select_bias, weights the
    unbiased s of the chosen experts.  Either way the weights are
    renormalized over the k and scaled by ``routed_scale``."""
    m = cfg.moe
    if m.scoring == "sigmoid":
        logits = jnp.dot(xf.astype(jnp.float32), p["router"],
                         precision=jax.lax.Precision.HIGHEST)
        gates = jax.nn.sigmoid(logits)
        _, top_e = jax.lax.top_k(gates + p["select_bias"], m.top_k)
        top_g = jnp.take_along_axis(gates, top_e, axis=-1)
    else:
        logits = (xf @ p["router"].astype(xf.dtype)).astype(jnp.float32)
        gates = jax.nn.softmax(logits, axis=-1)
        top_g, top_e = jax.lax.top_k(gates, m.top_k)  # [T, k]
    top_g = top_g / jnp.sum(top_g, axis=-1, keepdims=True) * m.routed_scale
    return top_g, top_e, gates


def _dispatch_capacity(cfg: ModelConfig, p, xf, top_g, top_e, C: int):
    """Padded scatter dispatch through an [E, C, d] buffer (tokens whose
    expert is over capacity are dropped)."""
    m = cfg.moe
    T, d = xf.shape
    E, k = m.n_experts, m.top_k

    # position of each (token, choice) inside its expert's capacity buffer
    e_flat = top_e.reshape(-1)  # [T*k]
    t_flat = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    g_flat = top_g.reshape(-1)
    onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - onehot,
                              e_flat[:, None], axis=1)[:, 0]
    keep = pos < C
    safe_e = jnp.where(keep, e_flat, E)  # out-of-range => dropped by scatter

    # [E, C] token index / combine weight per expert slot
    idx = jnp.zeros((E, C), jnp.int32).at[safe_e, pos].set(t_flat, mode="drop")
    wgt = jnp.zeros((E, C), jnp.float32).at[safe_e, pos].set(g_flat, mode="drop")

    xe = jnp.take(xf, idx.reshape(-1), axis=0).reshape(E, C, d)  # dispatch
    xe = _constrain(xe, ("data", None, None))
    h = _constrain(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"]),
                   ("data", None, "model"))
    u = _constrain(jnp.einsum("ecd,edf->ecf", xe, p["w_up"]),
                   ("data", None, "model"))
    ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, p["w_down"])
    ye = _constrain(ye, ("data", None, None))
    ye = ye * wgt[..., None].astype(ye.dtype)

    return jnp.zeros((T, d), ye.dtype).at[idx.reshape(-1)].add(
        ye.reshape(E * C, d))  # combine


def _experts(R: int, impl: str, xf, w, top_g, order, counts):
    """The held experts over the leading ``R`` rows of the sorted
    assignments ``order`` ([T·k] flat (token, choice) indices, held experts
    first), combined back into their tokens: [T, d].  Rows past the held
    experts' count (``sum(counts)``) are dead: the grouped GEMM gives them 0
    and their gate weight is 0."""
    T, d = xf.shape
    k = top_g.shape[-1]
    w_gate, w_up, w_down = w
    order = order[:R]
    src = order // k                                          # token per row
    live = jnp.arange(R) < jnp.sum(counts)

    xs = _constrain(jnp.take(xf, src, axis=0), ("data", None))  # [R, d]
    wdt = jnp.promote_types(xs.dtype, w_gate.dtype)
    xs = xs.astype(wdt)
    gm = lambda a, w: ops.grouped_matmul(a, w.astype(wdt), counts, impl=impl)
    h = _constrain(gm(xs, w_gate), ("data", "model"))
    u = _constrain(gm(xs, w_up), ("data", "model"))
    ys = gm(jax.nn.silu(h) * u, w_down)                       # [R, d]
    ys = _constrain(ys, ("data", None))
    g = jnp.where(live, top_g.reshape(-1)[order], 0.0)
    ys = ys * g[:, None].astype(ys.dtype)

    return jnp.zeros((T, d), ys.dtype).at[src].add(ys)        # combine


def _by_bound(fits, tight, full, *args):
    """``tight(*args)`` where ``fits``, else ``full(*args)`` under the scope
    ``moe_full_bound``; only the branch taken runs.  Under ``vmap`` (the
    executor's worker axis) the batch takes one branch, the tight one only
    where every member fits: a batched predicate would turn ``lax.cond``
    into a select that runs both."""
    def scoped_full(*a):
        with jax.named_scope(tracing.FULL_BOUND):
            return full(*a)

    @custom_vmap
    def run(fits, *args):
        return jax.lax.cond(fits, tight, scoped_full, *args)

    @run.def_vmap
    def _(axis_size, in_batched, fits, *args):
        axes = tuple(jax.tree.map(lambda b: 0 if b else None, b)
                     for b in in_batched[1:])
        batched = lambda f: jax.vmap(f, in_axes=axes, axis_size=axis_size)
        out = jax.lax.cond(jnp.all(fits), batched(tight), batched(scoped_full),
                           *args)
        return out, jax.tree.map(lambda _: True, out)

    return run(fits, *args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _bounded_experts(R_t: int, R: int, impl: str, xf, w, top_g, order,
                     counts):
    """``_experts`` at the tight bound ``R_t`` while the routed rows fit
    (``sum(counts) <= R_t``), else at the full bound ``R``: the same result.
    Its VJP saves the inputs alone and recomputes the branch taken, so no
    buffer of either bound outlives the call (differentiating a
    ``lax.cond`` would save both branches' residuals)."""
    return _bounded_fwd(R_t, R, impl, xf, w, top_g, order, counts)[0]


def _bounded_fwd(R_t, R, impl, xf, w, top_g, order, counts):
    at = lambda R: functools.partial(_experts, R, impl)
    out = _by_bound(jnp.sum(counts) <= R_t, at(R_t), at(R), xf, w, top_g,
                    order, counts)
    return out, (xf, w, top_g, order, counts)


def _bounded_bwd(R_t, R, impl, res, dy):
    xf, w, top_g, order, counts = res

    def grads(R):
        def f(xf, w, top_g, order, counts, dy):
            _, vjp = jax.vjp(lambda xf, w, top_g: _experts(
                R, impl, xf, w, top_g, order, counts), xf, w, top_g)
            return vjp(dy)
        return f

    return (*_by_bound(jnp.sum(counts) <= R_t, grads(R_t), grads(R), xf, w,
                       top_g, order, counts, dy), None, None)


_bounded_experts.defvjp(_bounded_fwd, _bounded_bwd)


def _dispatch_sorted(cfg: ModelConfig, p, xf, top_g, top_e, *,
                     impl: str = "auto"):
    """Dropless sort-based dispatch over the held experts: argsort the
    [T·k] assignments by held expert (the rest sort last), grouped GEMM
    over the leading rows of the sorted buffer, segment scatter-add back
    (``_experts``).  The leading rows are ``tight_rows`` while the rows
    routed here fit in them and ``held_rows`` otherwise; where the tight
    bound is no tighter, ``held_rows`` alone, with no branch."""
    m = cfg.moe
    T = xf.shape[0]
    E = m.n_held

    local = top_e.reshape(-1) - m.held_offset                 # [T*k]
    held = (local >= 0) & (local < E)
    key = jnp.where(held, local, E)
    order = jnp.argsort(key)                                  # stable
    counts = jnp.bincount(key, length=E + 1)[:E]              # segment sizes
    w = (p["w_gate"], p["w_up"], p["w_down"])
    R, R_t = held_rows(cfg, T), tight_rows(cfg, T)
    if R_t < R:
        return _bounded_experts(R_t, R, impl, xf, w, top_g, order, counts)
    return _experts(R, impl, xf, w, top_g, order, counts)


@jax.named_scope("moe")
def apply_moe(cfg: ModelConfig, p, x, *, train: bool = False,
              impl: str = "auto"):
    """x: [B, S, d] -> (out [B, S, d], aux_loss scalar)."""
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    E, k = m.n_experts, m.top_k
    xf = x.reshape(T, d)

    top_g, top_e, gates = route(cfg, p, xf)

    if m.dispatch == "capacity":
        out = _dispatch_capacity(cfg, p, xf, top_g, top_e,
                                 capacity(cfg, T, train=train))
    else:
        out = _dispatch_sorted(cfg, p, xf, top_g, top_e, impl=impl)
    out = out.reshape(B, S, d).astype(x.dtype)

    if m.scoring == "sigmoid":
        # the selection bias is the balancing mechanism: no auxiliary loss
        aux = jnp.zeros((), jnp.float32)
    else:
        # Switch-style load-balance auxiliary loss.  ``ce`` counts the
        # dispatched fraction over ALL k choices (normalized by k) so top-2
        # archs (dbrx, arctic) balance both slots; at k = 1 this reduces
        # exactly to the classic top-1 count (pinned by
        # tests/test_moe_dispatch.py).
        me = jnp.mean(gates, axis=0)  # mean router prob per expert
        ce = jnp.mean(jnp.sum(jax.nn.one_hot(top_e, E, dtype=jnp.float32),
                              axis=1), axis=0) / k
        aux = E * jnp.sum(me * ce)

    if m.dense_residual:
        out = out + apply_mlp(cfg, p["dense"], x)
    return out, aux
