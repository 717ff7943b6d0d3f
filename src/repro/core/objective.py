"""Pluggable min-max objectives for the CoDA executors.

The paper's construction — I collective-free local primal-dual steps, one
averaging per window — never looks inside the objective: it only needs a
scoring model h(w; x), a handful of per-worker dual scalars, and rules for
stepping/averaging them.  This module is that seam.  An ``Objective`` owns

  * ``init_duals(K)``   — the dual state as a dict pytree of [K] fp32 fields
                          (one slot per worker, like every CoDA variable);
  * ``loss(h, y, duals)`` — the saddle function F(w, duals; z), differentiable
                          in ``h`` and every dual leaf (use ``jax.custom_vjp``
                          where closed-form partials exist, as ``auc_F`` does);
  * ``dual_step``       — how dual gradients are applied: proximal descent for
                          fields in ``prox_refs`` (they get a ``ref_duals``
                          slot, reset at stage boundaries), projected descent
                          for fields in ``descent`` (min-player auxiliaries,
                          e.g. the DRO temperature), plain ascent for the rest
                          (the concave duals);
  * ``stage_duals``     — closed-form maximizer re-estimates at a stage
                          boundary (Alg. 1 lines 4-7: ``optimal_alpha``), one
                          fp32 scalar per ``stage_fields`` entry on the wire;
  * ``metric``          — the scalar the objective optimizes for reporting
                          (AUC, partial AUC), built as a mergeable
                          ``repro.metrics.streaming.Metric`` with
                          ``init``/``update``/``merge``/``finalize`` and two
                          backends: ``exact`` (materialise everything —
                          ``roc_auc``/``partial_auc`` below) and ``sketch``
                          (fixed-size streaming histogram).  The old bare
                          ``eval_metric`` callable is removed and raises.

Everything downstream — the vmap oracle and shard_map executors
(core/coda.py, core/coda_sharded.py), CODASCA control variates
(core/codasca.py), dtype-bucket payload accounting and int8 compression
(core/bucketing.py), sharding rules and the HLO payload asserts — works off
the *tree structure* of ``duals``, never off field names, so registering a
new objective touches exactly this file.

Registered objectives:

  * ``auc``      — the Ying et al. 2016 min-max AUC reformulation (paper
                   eq. 2): duals (a, b, α), fused one-pass loss kernel.
  * ``pauc_dro`` — one-way partial AUC via KL-regularized DRO over negatives
                   ("When AUC meets DRO", Zhu et al. 2022): the negative-side
                   expectation of the AUC surrogate is replaced by its KL-DRO
                   value at radius log(1/β) (β = the FPR budget), whose dual
                   temperature λ joins the dual state and is minimized by
                   projected descent; the loss gradient reweights negatives
                   by softmax(ℓ_j/λ) — hard negatives dominate, which is
                   exactly the FPR ≤ β head of the ROC curve.
  * ``bce``      — dual-free logit-space binary cross-entropy (the
                   baseline's loss minimization strawman): ``init_duals`` is
                   the empty tree and the same executors run it with zero
                   dual payload.

``auc_F`` is a differentiable fused primitive: forward and *all* partials
come from one pass over the scores (``kernels.ops.auc_loss`` — Pallas on TPU,
closed-form jnp elsewhere), wired into autodiff with ``jax.custom_vjp``.  The
closed-form partials are exactly the expressions in Appendix B (eq. 34) of
the paper restricted to the scalar head:

    ∂F/∂h = 2(1-p)(h-a)·1⁺ + 2p(h-b)·1⁻ + 2(1+α)(p·1⁻ − (1-p)·1⁺)
    ∂F/∂a = −2(1-p)(h-a)·1⁺        ∂F/∂b = −2p(h-b)·1⁻
    ∂F/∂α = 2(p·h·1⁻ − (1-p)·h·1⁺) − 2p(1-p)α
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops

_EPS = 1e-12


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def auc_F(h, y, a, b, alpha, p, impl="auto"):
    """Mean of F(w,a,b,α;z) over the batch.  h: [T] scores, y: [T] ∈ {0,1}.
    ``impl`` picks the kernel (``kernels.ops.dispatch``)."""
    loss, *_ = kops.auc_loss(h, y, a, b, alpha, p, impl=impl)
    return loss


def _fwd(h, y, a, b, alpha, p, impl):
    loss, dh, da, db, dalpha = kops.auc_loss(h, y, a, b, alpha, p, impl=impl)
    return loss, (dh.astype(h.dtype), da, db, dalpha)


def _bwd(impl, res, ct):
    dh, da, db, dalpha = res
    return (ct * dh, None, ct * da, ct * db, ct * dalpha, None)


auc_F.defvjp(_fwd, _bwd)


def optimal_alpha(h, y, eps: float = _EPS):
    """Closed-form maximizer α*(v) = E[h|y=-1] − E[h|y=1] (paper eq. 8),
    estimated on a batch — this is Algorithm 1 lines 4–7 for one machine."""
    h = h.astype(jnp.float32)
    pos = y.astype(jnp.float32)
    neg = 1.0 - pos
    mean_neg = jnp.sum(h * neg) / jnp.maximum(jnp.sum(neg), eps)
    mean_pos = jnp.sum(h * pos) / jnp.maximum(jnp.sum(pos), eps)
    return mean_neg - mean_pos


# --------------------------------------------------------------------------
# evaluation metrics
# --------------------------------------------------------------------------
def roc_auc(scores, labels):
    """Exact (tie-aware) empirical AUC via rank statistics.

    Tied scores contribute 1/2 per pair (average ranks).  Degenerate
    single-class batches (no positives or no negatives) return 0.0 — there
    are no pairs to rank, and callers treat the value as "undefined, worst".
    Pinned against the O(n²) pairwise oracle in tests/test_objective.py.
    """
    s = scores.astype(jnp.float32)
    y = labels.astype(jnp.float32)
    order = jnp.argsort(s)
    ss = s[order]
    ranks1 = jnp.arange(1, s.shape[0] + 1, dtype=jnp.float32)
    # average ranks over ties
    first = jnp.searchsorted(ss, ss, side="left").astype(jnp.float32) + 1
    last = jnp.searchsorted(ss, ss, side="right").astype(jnp.float32)
    avg_rank_sorted = 0.5 * (first + last)
    ranks = jnp.zeros_like(ranks1).at[order].set(avg_rank_sorted)
    n_pos = jnp.sum(y)
    n_neg = jnp.sum(1.0 - y)
    sum_pos_ranks = jnp.sum(ranks * y)
    return (sum_pos_ranks - n_pos * (n_pos + 1) / 2) / jnp.maximum(n_pos * n_neg, _EPS)


def partial_auc(scores, labels, beta: float = 0.3):
    """One-way partial AUC at FPR ≤ ``beta``, normalized to [0, 1].

    Positives are ranked against only the hardest ⌈β·n⁻⌉ negatives (the
    top-scoring ones — the negatives that populate the FPR ≤ β head of the
    ROC curve); ties count 1/2.  Runs in NumPy (an eval-time metric, never
    traced).  Degenerate single-class inputs return 0.0, matching
    ``roc_auc``'s convention.  Pinned against the O(n²) pairwise oracle in
    tests/test_objective.py.
    """
    s = np.asarray(scores, np.float64)
    y = np.asarray(labels, np.float64)
    sp = s[y > 0.5]
    sn = s[y <= 0.5]
    if len(sp) == 0 or len(sn) == 0:
        return 0.0
    k = max(1, int(np.ceil(beta * len(sn))))
    hard = np.sort(sn)[::-1][:k]        # hardest k negatives by score
    # tie-aware AUC of positives vs the hard-negative subset, via ranks on
    # the pooled vector (same formula as roc_auc, subset-restricted)
    pooled = np.concatenate([sp, hard])
    order = np.argsort(pooled, kind="mergesort")
    sorted_ = pooled[order]
    first = np.searchsorted(sorted_, sorted_, side="left") + 1
    last = np.searchsorted(sorted_, sorted_, side="right")
    ranks = np.empty_like(pooled)
    ranks[order] = 0.5 * (first + last)
    n_pos = float(len(sp))
    sum_pos_ranks = float(ranks[:len(sp)].sum())
    return float((sum_pos_ranks - n_pos * (n_pos + 1) / 2) / (n_pos * k))


# --------------------------------------------------------------------------
# the Objective seam
# --------------------------------------------------------------------------
class Objective:
    """One min-max objective: dual state + loss + update/boundary rules.

    Subclasses set the class attributes and implement ``loss`` /
    ``stage_duals``; ``dual_step`` has a generic implementation driven by
    the field sets (override ``project`` for constrained descent fields).
    Instances are cheap immutable config holders — built per trace via
    ``for_config`` and closed over, never passed as jit arguments.
    """

    name: str = ""
    prox_refs: tuple[str, ...] = ()     # duals under proximal regularization
    descent: tuple[str, ...] = ()       # min-player duals (projected descent)
    stage_fields: tuple[str, ...] = ()  # duals re-estimated at stage ends
    metric_name: str = "auc"

    def init_duals(self, K: int) -> dict[str, jax.Array]:
        raise NotImplementedError

    def loss(self, h, y, duals):
        """F(w, duals; z) for one worker's batch: h [T] scores, y [T] labels,
        duals a dict of scalars (the worker axis is vmapped away)."""
        raise NotImplementedError

    def dual_step(self, duals, grads, ref_duals, eta, gamma):
        """Apply one step of dual gradients: prox for ``prox_refs`` fields
        (against their ``ref_duals`` slot), projected descent for
        ``descent`` fields, ascent for the concave rest."""
        new = {}
        for k, v in duals.items():
            if k in self.prox_refs:
                new[k] = (gamma * (v - eta * grads[k])
                          + eta * ref_duals[k]) / (eta + gamma)
            elif k in self.descent:
                new[k] = self.project(k, v - eta * grads[k])
            else:
                new[k] = v + eta * grads[k]
        return new

    def project(self, field: str, value):
        """Feasibility projection for ``descent`` fields (identity here)."""
        return value

    def stage_duals(self, h, y, duals) -> dict[str, jax.Array]:
        """Closed-form re-estimates for ``stage_fields`` from a fresh batch
        (one machine's view; the caller worker-means the results)."""
        return {}

    def metric(self, backend: str = "exact", **kw):
        """Build this objective's reporting metric as a mergeable
        ``repro.metrics.streaming.Metric`` (``backend`` ∈ {exact, sketch};
        sketch kwargs ``bins``/``lo``/``hi`` pass through)."""
        from repro.metrics import streaming  # deferred: metrics finalizes here

        return streaming.make_metric(self.metric_name, backend, **kw)

    @property
    def eval_metric(self):
        raise AttributeError(
            "Objective.eval_metric was removed by the Metric redesign: use "
            "Objective.metric(backend) — a mergeable Metric with init/"
            "update/merge/finalize (repro.metrics.streaming); one-shot "
            "evaluation is metric('exact').compute(scores, labels).")


def _zeros(K: int):
    return jnp.zeros((K,), jnp.float32)


class AUCObjective(Objective):
    """Ying et al. min-max AUC (paper eq. 2): duals (a, b, α) where a/b track
    the class-conditional score means (proximal minimization) and α is the
    concave dual with closed-form stage-end maximizer ``optimal_alpha``."""

    name = "auc"
    prox_refs = ("a", "b")
    stage_fields = ("alpha",)
    metric_name = "auc"

    def __init__(self, p_pos: float = 0.5, impl: str = "auto"):
        self.p_pos = p_pos
        self.impl = impl

    def init_duals(self, K: int):
        return {"a": _zeros(K), "b": _zeros(K), "alpha": _zeros(K)}

    def loss(self, h, y, duals):
        return auc_F(h, y, duals["a"], duals["b"], duals["alpha"], self.p_pos,
                     self.impl)

    def stage_duals(self, h, y, duals):
        return {"alpha": optimal_alpha(h, y)}


class PAUCDROObjective(Objective):
    """One-way partial AUC at FPR ≤ β as a KL-DRO min-max.

    The AUC surrogate's negative-side expectation E⁻[ℓ_j],
    ℓ_j = (h_j − b)² + 2(1+α)h_j, is replaced by its KL-DRO value

        min_{λ ≥ λ_min}  λ·log(1/β) + λ·log E⁻[exp(ℓ_j / λ)]

    — the dual of  max_{q : KL(q‖uniform) ≤ log(1/β)} Σ_j q_j ℓ_j.  The
    gradient through the log-sum-exp reweights negatives by
    q_j ∝ exp(ℓ_j/λ): at small λ only the hardest (top-scoring) negatives
    matter, which is the FPR ≤ β head of the ROC curve; λ → ∞ recovers the
    full-AUC objective.  λ rides the dual state (field ``lam``, projected
    descent at floor ``lam_min``) so the executors, CODASCA variates, and
    payload accounting treat it like any other dual — the dual tree simply
    has four fields instead of three.  a/b/α keep their AUC roles, with α's
    stage-end maximizer computed under the DRO weights.
    """

    name = "pauc_dro"
    prox_refs = ("a", "b")
    descent = ("lam",)
    stage_fields = ("alpha",)
    metric_name = "pauc"

    def __init__(self, p_pos: float = 0.5, beta: float = 0.3,
                 lam_init: float = 1.0, lam_min: float = 0.05):
        self.p_pos = p_pos
        self.beta = beta
        self.lam_init = lam_init
        self.lam_min = lam_min
        self.rho = float(np.log(1.0 / beta))

    def init_duals(self, K: int):
        return {"a": _zeros(K), "b": _zeros(K), "alpha": _zeros(K),
                "lam": jnp.full((K,), self.lam_init, jnp.float32)}

    def _neg_losses(self, h, duals):
        return (h - duals["b"]) ** 2 + 2.0 * (1.0 + duals["alpha"]) * h

    def loss(self, h, y, duals):
        p = self.p_pos
        h = h.astype(jnp.float32)
        pos = y.astype(jnp.float32)
        neg = 1.0 - pos
        n_pos = jnp.sum(pos)
        n_neg = jnp.sum(neg)
        a, alpha = duals["a"], duals["alpha"]
        lam = jnp.maximum(duals["lam"], self.lam_min)
        mean_pos = lambda z: jnp.sum(z * pos) / jnp.maximum(n_pos, _EPS)
        pos_side = ((1.0 - p) * mean_pos((h - a) ** 2)
                    - 2.0 * (1.0 + alpha) * (1.0 - p) * mean_pos(h)
                    - p * (1.0 - p) * alpha * alpha)
        # KL-DRO value of the negative-side losses: λρ + λ·log E⁻[exp(ℓ/λ)].
        # Double-where guard: an all-positive batch (Dirichlet-starved
        # shards hit this) would make logsumexp(b=0) a NaN whose *gradient*
        # leaks through a single jnp.where — so the inner computation runs
        # on a safe uniform mask and the outer where zeroes the value.
        has_neg = n_neg > 0
        neg_safe = jnp.where(has_neg, neg, jnp.ones_like(neg))
        lse = jax.scipy.special.logsumexp(self._neg_losses(h, duals) / lam,
                                          b=neg_safe)
        dro = lam * (self.rho + lse - jnp.log(jnp.sum(neg_safe)))
        return pos_side + jnp.where(has_neg, p * dro, 0.0)

    def project(self, field: str, value):
        return jnp.maximum(value, self.lam_min)

    def stage_duals(self, h, y, duals):
        """α* = Ê_q[h | y=-1] − E[h | y=1] under the current DRO weights
        q_j ∝ exp(ℓ_j/λ) — ``optimal_alpha`` with the negative expectation
        tilted toward the hard negatives."""
        h = h.astype(jnp.float32)
        pos = y.astype(jnp.float32)
        neg = 1.0 - pos
        has_neg = jnp.sum(neg) > 0
        neg_safe = jnp.where(has_neg, neg, jnp.ones_like(neg))
        lam = jnp.maximum(duals["lam"], self.lam_min)
        logits = self._neg_losses(h, duals) / lam
        logits = jnp.where(neg_safe > 0.5, logits, -jnp.inf)
        q = jax.nn.softmax(logits)
        mean_neg = jnp.where(has_neg, jnp.sum(q * h), 0.0)
        mean_pos = jnp.sum(h * pos) / jnp.maximum(jnp.sum(pos), _EPS)
        return {"alpha": mean_neg - mean_pos}

    def metric(self, backend: str = "exact", **kw):
        kw.setdefault("beta", self.beta)
        from repro.metrics import streaming

        return streaming.make_metric("pauc", backend, **kw)


class BCEObjective(Objective):
    """Dual-free binary cross-entropy — the introduction's "standard loss
    minimization" strawman, routed through the same seam: the dual tree is
    empty, so the executors run pure distributed SGD with zero dual payload
    (``baselines.bce_step`` shares this loss instead of its own closure).

    The scores ``h`` every executor feeds this are the *unbounded*
    ``score_head`` logits, so the loss is logit-space BCE
    (``-[y·log σ(h) + (1−y)·log σ(−h)]`` via the stable ``log_sigmoid``).
    The old form clipped ``h`` into (1e-6, 1−1e-6) and took logs — treating
    a logit as a probability — so any score outside (0, 1) saturated the
    clip and its gradient vanished exactly; pinned against the explicit
    sigmoid+log oracle in tests/test_objective.py."""

    name = "bce"
    metric_name = "auc"

    def __init__(self, p_pos: float = 0.5):
        self.p_pos = p_pos  # unused by the loss; kept for a uniform ctor

    def init_duals(self, K: int):
        return {}

    def loss(self, h, y, duals):
        h = h.astype(jnp.float32)
        y = y.astype(jnp.float32)
        return -jnp.mean(y * jax.nn.log_sigmoid(h)
                         + (1.0 - y) * jax.nn.log_sigmoid(-h))


REGISTRY = {"auc": AUCObjective, "pauc_dro": PAUCDROObjective,
            "bce": BCEObjective}


def names() -> tuple[str, ...]:
    return tuple(REGISTRY)


def for_config(ccfg) -> Objective:
    """Build the configured objective from a ``CoDAConfig``."""
    name = getattr(ccfg, "objective", "auc")
    if name == "pauc_dro":
        return PAUCDROObjective(p_pos=ccfg.p_pos, beta=ccfg.pauc_beta)
    if name == "auc":
        return AUCObjective(p_pos=ccfg.p_pos, impl=ccfg.impl)
    return REGISTRY[name](p_pos=ccfg.p_pos)
