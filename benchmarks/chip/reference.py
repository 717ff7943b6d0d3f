"""Plain reference of a CoDA window and the comparison that decides
``correct``.

The reference imports nothing of the program.  One window is, for every
worker k and each of its I local steps, the paper's min-max AUC loss

    F = mean_i [(1-p)(h_i-a)^2 1[y_i=1] + p(h_i-b)^2 1[y_i=0]
                + 2(1+alpha)(p h_i 1[y_i=0] - (1-p) h_i 1[y_i=1])] - p(1-p) alpha^2

differentiated by autodiff, the proximal primal step
v <- (gamma (v - eta g) + eta v0) / (eta + gamma), proximal descent on a
and b, ascent on alpha, and then one average of the parameters and duals
over the workers.  It runs in float32 at the highest matmul precision; the
control runs the same code in bfloat16.

Readings, taken alike from the program (through the harness's probe) and
from the reference after windows 1 and 3 of a run: every local step's loss
on every worker, and per leaf the norm of the change since the start (the
duals a, b and alpha are leaves too).  The reference also gives the norm of
its first gradient per leaf, which decides the leaves that count.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# Leaves whose first reference gradient is under this share of the median
# leaf's move by round-off alone (a key bias under softmax, a head the score
# never reads) and are left out of the norm comparisons.
DEAD_LEAF_SHARE = 1e-3


def leaf_names(tree) -> list[str]:
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def auc_loss(h, y, a, b, alpha, p, n):
    """Sum over a block of rows of F's per-row terms, divided by the batch
    size ``n``; the -p(1-p)alpha^2 term is added once by the caller."""
    pos = y
    neg = 1.0 - y
    f = ((1 - p) * (h - a) ** 2 * pos + p * (h - b) ** 2 * neg
         + 2 * (1 + alpha) * (p * h * neg - (1 - p) * h * pos))
    return jnp.sum(f) / n


def make_window(score, cfg: dict, *, eta: float, gamma: float, p: float,
                block_rows: int, dtype=jnp.float32, half_batch: bool = False,
                average: bool = True):
    """A jitted reference window: (params [K, ...], duals {a, b, alpha: [K]},
    v0 (one worker's start), window batch [I, K, B, ...]) -> (params, duals,
    losses [I, K], first-step gradient norms per leaf).

    ``half_batch`` and ``average=False`` plant two faults the comparison
    must catch: half of every batch left out with the mean taken over the
    rest, and the average over workers left out."""
    c = lambda x: jnp.asarray(x, dtype)

    def worker_step(params, duals, batch, v0):
        n = batch["labels"].shape[0]
        if half_batch:
            n //= 2
            batch = jax.tree_util.tree_map(lambda l: l[:n], batch)
        nb = max(n // block_rows, 1)
        rows = n // nb
        blocks = jax.tree_util.tree_map(
            lambda l: l.reshape((nb, rows) + l.shape[1:]), batch)

        def block_loss(prm, a, b, alpha, blk):
            h = score(prm, blk, cfg, dtype).astype(dtype)
            return auc_loss(h, blk["labels"].astype(dtype), a, b, alpha,
                            c(p), c(n))

        grad = jax.value_and_grad(block_loss, argnums=(0, 1, 2, 3))
        a, b, alpha = duals["a"], duals["b"], duals["alpha"]

        def acc(carry, blk):
            loss, g = carry
            lb, gb = grad(params, a, b, alpha, blk)
            return (loss + lb, jax.tree_util.tree_map(jnp.add, g, gb)), None

        zero = jax.tree_util.tree_map(jnp.zeros_like, (params, a, b, alpha))
        (loss, (gp, ga, gb, gal)), _ = jax.lax.scan(
            acc, (c(0.0), zero), blocks)
        loss = loss - c(p) * (1 - c(p)) * alpha ** 2
        gal = gal - 2 * c(p) * (1 - c(p)) * alpha
        prox = lambda v, g, ref: (c(gamma) * (v - c(eta) * g)
                                  + c(eta) * ref) / (c(eta) + c(gamma))
        params = jax.tree_util.tree_map(
            lambda v, g, r: prox(v, g.astype(dtype), r.astype(dtype)),
            params, gp, v0)
        duals = {"a": prox(a, ga, c(0.0)), "b": prox(b, gb, c(0.0)),
                 "alpha": alpha + c(eta) * gal}
        gnorm = [jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32))))
                 for g in jax.tree_util.tree_leaves(
                     {"params": gp, "duals": {"a": ga, "b": gb,
                                              "alpha": gal}})]
        return params, duals, loss, jnp.stack(gnorm)

    def window(params, duals, v0, wb):
        v0 = jax.tree_util.tree_map(lambda l: l.astype(dtype), v0)

        def step(carry, b_t):
            prm, dl = carry
            prm, dl, loss, gn = jax.vmap(worker_step, in_axes=(0, 0, 0, None))(
                prm, dl, b_t, v0)
            return (prm, dl), (loss, gn)

        (params, duals), (losses, gnorms) = jax.lax.scan(
            step, (params, duals), wb)
        if average:
            mean = lambda l: jnp.broadcast_to(
                jnp.mean(l.astype(jnp.float32), axis=0, keepdims=True),
                l.shape).astype(l.dtype)
            params = jax.tree_util.tree_map(mean, params)
            duals = jax.tree_util.tree_map(mean, duals)
        return params, duals, losses, jnp.mean(gnorms[0], axis=0)

    return jax.jit(window, donate_argnums=(0, 1))


def change_norms(params, duals, start):
    """Per leaf of {"params", "duals"}: the norm of the change from
    ``start`` (the parameters' start, shaped like ``params``; the duals
    start at 0), taken over all K worker copies and divided by sqrt(K), and
    the largest norm of one worker's distance from the workers' mean."""
    K = jax.tree_util.tree_leaves(params)[0].shape[0]
    start = {"params": start,
             "duals": jax.tree_util.tree_map(jnp.zeros_like, duals)}
    now = {"params": params, "duals": duals}
    f32 = lambda l: l.astype(jnp.float32)
    change = [jnp.sqrt(jnp.sum(jnp.square(f32(x) - f32(s)))) / K ** 0.5
              for x, s in zip(jax.tree_util.tree_leaves(now),
                              jax.tree_util.tree_leaves(start))]
    spread = [jnp.max(jnp.sqrt(jnp.sum(jnp.square(
                  (f32(x) - jnp.mean(f32(x), axis=0, keepdims=True))
                  .reshape(K, -1)), axis=1)))
              for x in jax.tree_util.tree_leaves(now)]
    return jnp.stack(change), jnp.stack(spread)


def run(score, cfg: dict, v0, batches: list, *, K: int, eta: float,
        gamma: float, p: float, block_rows: int, dtype=jnp.float32,
        half_batch: bool = False, average: bool = True) -> dict:
    """Three reference windows from the start ``v0`` over ``batches`` (host
    window batches [I, K, B, ...]).  Returns the readings as NumPy arrays:
    ``loss`` [3, I, K], ``change1``/``change3``/``spread3`` per leaf, and
    ``grad1``, the first step's gradient norm per leaf (mean over workers)."""
    window = make_window(score, cfg, eta=eta, gamma=gamma, p=p,
                         block_rows=block_rows, dtype=dtype,
                         half_batch=half_batch, average=average)
    stack = lambda t: jax.tree_util.tree_map(
        lambda l: jnp.broadcast_to(l.astype(dtype)[None], (K,) + l.shape), t)
    norms = jax.jit(lambda p, d, v: change_norms(p, d, stack(v)))
    params = stack(v0)
    duals = {f: jnp.zeros((K,), dtype) for f in ("a", "b", "alpha")}
    out = {"loss": []}
    for w, wb in enumerate(batches):
        params, duals, losses, gn = window(params, duals, v0,
                                           jax.device_put(wb))
        out["loss"].append(np.asarray(losses, np.float64))
        if w == 0:
            out["grad1"] = np.asarray(gn, np.float64)
        if w in (0, 2):
            ch, sp = norms(params, duals, v0)
            out[f"change{w + 1}"] = np.asarray(ch, np.float64)
            out[f"spread{w + 1}"] = np.asarray(sp, np.float64)
    out["loss"] = np.stack(out["loss"])
    return out


# ---------------------------------------------------------------------------
# comparison
# ---------------------------------------------------------------------------
def live_leaves(grad1) -> np.ndarray:
    """Mask of the leaves the norm comparisons count (see DEAD_LEAF_SHARE)."""
    g = np.asarray(grad1)
    return g >= DEAD_LEAF_SHARE * np.median(g)


def _leaf_gaps(got, want, live) -> np.ndarray:
    """Each live leaf's gap between two norms, against the larger of that
    leaf's reference norm and the median live leaf's."""
    want, got = np.asarray(want)[live], np.asarray(got)[live]
    return np.abs(got - want) / np.maximum(want, np.median(want))


def worst_leaves(got: dict, want: dict, key: str, names: list[str],
                 n: int = 3) -> list:
    """The ``n`` live leaves with the widest gap of ``key`` (``change1`` or
    ``change3``): name, gap, program's and reference's norm, and the
    reference's first gradient norm."""
    live = live_leaves(want["grad1"])
    w, g = np.asarray(want[key]), np.asarray(got[key])
    gap = np.abs(g - w) / np.maximum(w, np.median(w[live]))
    order = [i for i in np.argsort(-gap) if live[i]][:n]
    return [(names[i], float(gap[i]), float(g[i]), float(w[i]),
             float(want["grad1"][i])) for i in order]


def compare(got: dict, want: dict, *, K: int) -> dict:
    """Every number a cell may compare (its ``checks/<cell>.json`` names
    the ones it does): the worst relative gap of a step's loss; the worst
    leaf's gap of the change after window 1 and after window 3, and the
    median leaf's; and (K > 1) the largest distance of one worker from the
    workers' mean after window 3, against the reference's change of that
    leaf."""
    live = live_leaves(want["grad1"])
    lw = np.asarray(want["loss"])
    lg = np.asarray(got["loss"]).reshape(lw.shape)
    g1 = _leaf_gaps(got["change1"], want["change1"], live)
    g3 = _leaf_gaps(got["change3"], want["change3"], live)
    out = {
        "loss_gap": float(np.max(np.abs(lg - lw)
                                 / np.maximum(np.abs(lw), np.mean(np.abs(lw))))),
        "update1_gap": float(np.max(g1)),
        "change3_gap": float(np.max(g3)),
        "update1_median_gap": float(np.median(g1)),
        "change3_median_gap": float(np.median(g3)),
    }
    if K > 1:
        w3 = np.asarray(want["change3"])[live]
        spread = np.asarray(got["spread3"])[live]
        out["worker_spread"] = float(np.max(spread / np.maximum(
            w3, np.median(w3))))
    return out
