"""Sharded CoDA executor: real mesh-parallel training via ``shard_map``.

The vmap oracle in ``core/coda.py`` *simulates* the K-worker axis as a
batched array axis on one device; nothing about the paper's communication
claims is real there.  This module lays the worker axis over actual mesh
devices (``launch/mesh.coda_worker_axes`` via ``sharding/rules.py``) and
runs the window under ``shard_map``, so the lowered HLO is the paper's
Algorithm 2 made literal:

  * the I local primal-dual steps contain **zero** collectives — each worker
    shard runs them on its own devices;
  * the periodic averaging is **one** all-reduce: every state tensor
    (params + the objective's dual tree, core/objective.py) is flattened
    and concatenated into a single bucket per dtype, locally pre-averaged,
    and ``lax.pmean``-ed over the worker axes.  The bucket layout is
    derived from tree structure, so any registered objective's duals ride
    it.  With the default fp32 state that is exactly one all-reduce whose
    operand bytes equal ``coda.model_bytes(state)`` — asserted against the
    compiled HLO in tests/test_coda_sharded.py;
  * with ``CoDAConfig(avg_compress="int8")`` only the int8 payload plus one
    fp32 scale per tensor cross the wire (an s8 all-gather + f32 all-gather
    pair), cutting wire bytes ~4x vs fp32 at ~0.4% quantization noise.

Worker placement follows ``rules.worker_partition``: the "replica" policy
shards workers over (pod?, data); "fsdp" over (pod) only.  When K does not
divide the worker axes (e.g. K=1, the PPD-SG degenerate case) the state is
replicated instead — the executor stays correct with zero collectives.
Within-worker tensor/FSDP parallelism *inside* the manual region is the
multi-host follow-on tracked in ROADMAP.md; trailing dims stay replicated
here.

Step functions are jitted once per window length with the state buffer
donated; ``place(state)`` device_puts the state onto the mesh so the loop
steps are pure buffer-in/buffer-out.  Equivalence with the vmap oracle is
tested to fp32 tolerance for both policies and the K=1 / I=1 degenerate
cases on 8 forced host devices.

``CoDAConfig(algorithm="codasca")`` swaps the window body for the control-
variate corrected variant (core/codasca.py): still zero collectives inside
the I local steps, still ONE all-reduce per window — the variate refresh
rides the same bucket, doubling its payload (tests/test_codasca.py).

``CoDAConfig(overlap_chunks=C > 0)`` adds the OVERLAPPED schedule: fit()
feeds fused two-window pairs (``window_pair_fn``) in which each averaging
lowers as C ppermute ring chains per dtype bucket
(core/bucketing.ring_mean_buckets) instead of a blocking pmean.  Inside
the fused module the first window's ring hops have only chunk-level data
dependencies against the second window's local steps, so XLA's async
collective-permute scheduling can hide the first averaging's wire time
under compute — the compiled artifact is asserted to be exactly C·2·(R−1)
``collective-permute`` chains per ring interleaved with dot compute and
NO all-reduce (tests/test_overlap.py, analysis/hlo.verify_overlapped_
window).  The ring mean is the same mean; the blocking path stays the
default and the two agree to fp32 tolerance.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import AxisType, Mesh, NamedSharding

from repro.configs.base import ModelConfig
from repro.core import bucketing, coda
from repro.sharding import rules

# The bucketed cross-worker averaging (the one all-reduce per window) lives
# in core/bucketing.py so the vmap oracle and this executor run the same
# arithmetic; the alias keeps the historical test surface.
_bucketed_average = bucketing.average_state


# --------------------------------------------------------------------------
# executor
# --------------------------------------------------------------------------
class ShardedExecutor:
    """Mesh-parallel CoDA: same surface as ``coda.VmapExecutor``.

    ``window_step`` returns per-worker losses ``[I, K]`` (not the oracle's
    worker-mean ``[I]``): reducing them would cost a second all-reduce in
    the hot window, and the per-worker spread is itself the data-
    heterogeneity signal.  Take ``losses.mean(axis=1)`` to compare.
    """

    def __init__(self, mcfg: ModelConfig, ccfg: coda.CoDAConfig, mesh, *,
                 policy: str = "replica", donate: bool = True):
        # the executor's specs are GSPMD (Auto) shardings; jax.make_mesh
        # types its axes Explicit by default, which would make every state
        # leaf carry its sharding in its type and refuse plain indexing
        mesh = Mesh(mesh.devices, mesh.axis_names,
                    axis_types=(AxisType.Auto,) * len(mesh.axis_names))
        self.mcfg, self.ccfg, self.mesh, self.policy = mcfg, ccfg, mesh, policy
        self.worker_axes = rules.worker_partition(mesh, policy, ccfg.n_workers)
        self._donate = (0,) if donate else ()
        self._fns = {}
        if ccfg.overlap_chunks and len(self.worker_axes) > 1:
            raise ValueError(
                "overlap_chunks needs the worker axis on ONE mesh axis (a "
                f"ppermute ring has a single total order); partition "
                f"{self.worker_axes} spans {len(self.worker_axes)} axes — "
                "use the fsdp policy or a single-pod mesh")

    def _ring_spec(self):
        """The RingSpec the overlapped averaging runs with, or None when
        overlap is off / there is no wire (replicated K=1 degenerate)."""
        if not self.ccfg.overlap_chunks or not self.worker_axes:
            return None
        ax = self.worker_axes[0]
        return bucketing.RingSpec(ax, self.mesh.shape[ax],
                                  self.ccfg.overlap_chunks)

    @property
    def overlap_pairs(self) -> bool:
        """True when fit() should feed fused window pairs (the overlapped
        schedule).  False on the degenerate no-wire partitions, where a
        ring would be pure overhead."""
        return self._ring_spec() is not None

    # -- spec plumbing ----------------------------------------------------
    def state_shardings(self, state):
        specs = rules.shardmap_state_specs(state, self.mesh, self.policy)
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), specs)

    def place(self, state: coda.CoDAState) -> coda.CoDAState:
        return jax.device_put(state, self.state_shardings(state))

    def _key(self, tag, *trees):
        return (tag,) + tuple(
            (jax.tree_util.tree_structure(t),
             tuple(l.ndim for l in jax.tree_util.tree_leaves(t)))
            for t in trees)

    # -- window -----------------------------------------------------------
    def _one_window(self, st, bt, eta, *, communicate, ring, fl=None):
        """One window's worth of per-shard work: I local steps + (optionally)
        the combined averaging — blocking pmean bucket by default, chunked
        ppermute rings when ``ring`` is given, the masked participant mean
        when ``fl`` (the per-window fault vectors, sliced to this shard's
        workers) is given.  Runs INSIDE shard_map."""
        mcfg, ccfg, wa = self.mcfg, self.ccfg, self.worker_axes
        if ccfg.algorithm == "codasca":
            from repro.core import codasca
            return codasca.run_window(mcfg, ccfg, st, bt, eta, wa=wa,
                                      communicate=communicate, ring=ring,
                                      faults=fl)

        def step(s, b):
            return coda.local_step(mcfg, ccfg, s, b, eta)

        from repro import flags
        start_params = st["params"]
        st, losses = jax.lax.scan(step, st, bt, unroll=flags.scan_unroll())
        if communicate:
            if fl is not None:
                st = bucketing.masked_average_state(
                    st, fl, wa, ccfg.avg_compress or None, ring=ring)
            else:
                st = bucketing.average_state(st, wa,
                                             ccfg.avg_compress or None,
                                             ring=ring,
                                             n_workers=ccfg.n_workers)
            if ccfg.server_momentum:  # rejected with faults at config time
                st = coda.server_momentum_step(st, start_params,
                                               ccfg.server_momentum)
        return st, losses  # losses: [I, K_loc]

    def _fault_specs(self, lead, *, paired: bool = False):
        """PartitionSpecs for the fault-vector dict: each [K] vector is
        sharded over the worker axes exactly like a state leading axis, so
        every shard sees its own workers' weights ([2, K] leaves under the
        fused pair get the worker axis second)."""
        from jax.sharding import PartitionSpec as P
        spec = P(None, lead) if paired else P(lead)
        return {"weights": spec, "resync": spec}

    def window_fn(self, state, wb, *, communicate: bool = True):
        """The jitted window step for these arg structures (also the hook
        the HLO tests use: ``.lower(state, wb, eta)`` — with the fault
        vectors as a fourth traced arg when ``ccfg.faults_enabled``)."""
        key = self._key(("window", communicate), state, wb)
        if key in self._fns:
            return self._fns[key]
        lead = self.worker_axes if self.worker_axes else None
        faulty = self.ccfg.faults_enabled

        if faulty:
            def body(st, bt, eta, fl):
                return self._one_window(st, bt, eta, communicate=communicate,
                                        ring=None, fl=fl)
        else:
            def body(st, bt, eta):
                return self._one_window(st, bt, eta, communicate=communicate,
                                        ring=None)

        st_specs = rules.shardmap_state_specs(state, self.mesh, self.policy)
        bt_specs = rules.shardmap_batch_specs(wb, self.mesh, self.policy,
                                              self.ccfg.n_workers,
                                              worker_dim=1)
        from jax.sharding import PartitionSpec as P
        in_specs = (st_specs, bt_specs, P())
        if faulty:
            in_specs = in_specs + (self._fault_specs(lead),)
        sm = _shard_map(body, mesh=self.mesh,
                        in_specs=in_specs,
                        out_specs=(st_specs, P(None, lead)),
                        check_vma=False)
        fn = jax.jit(sm, donate_argnums=self._donate)
        self._fns[key] = fn
        return fn

    def window_step(self, state, wb, eta, *, communicate: bool = True,
                    faults=None):
        fn = self.window_fn(state, wb, communicate=communicate)
        if self.ccfg.faults_enabled:
            if faults is None:
                raise ValueError(
                    "CoDAConfig enables fault injection; window_step needs "
                    "the per-window fault vectors (coda.fit builds them "
                    "from the FaultPlan)")
            return fn(state, wb, eta, faults)
        if faults is not None:
            raise ValueError(
                "fault vectors passed but CoDAConfig has fault injection "
                "disabled (set participation / straggler / crash knobs)")
        return fn(state, wb, eta)

    # -- fused window pair (the overlapped schedule) ----------------------
    def window_pair_fn(self, state, wb2, *, communicate: bool = True):
        """Two windows fused into ONE compiled unit, with every averaging
        lowered as chunked ppermute rings (``CoDAConfig.overlap_chunks``).

        ``wb2`` leaves carry a leading pair axis: [2, I, K, B, ...].  Inside
        the fused module the first window's ring chains have no barrier
        against the second window's local-step compute — only chunk-level
        data dependencies — so XLA's async collective-permute scheduling
        can hide the first averaging's wire time entirely (that is the
        ``overlapped_bytes`` half of the fit accounting; the second
        window's ring, with nothing after it, stays exposed).  The math is
        the blocking path's math: same bucket, same mean, asserted to fp32
        tolerance in tests/test_overlap.py.
        """
        key = self._key(("pair", communicate), state, wb2)
        if key in self._fns:
            return self._fns[key]
        ring = self._ring_spec()
        lead = self.worker_axes if self.worker_axes else None
        faulty = self.ccfg.faults_enabled

        def run_pair(st, bt2, eta, fl2=None):
            take = lambda t, i: jax.tree_util.tree_map(lambda l: l[i], t)
            flt = lambda i: None if fl2 is None else take(fl2, i)
            st, l1 = self._one_window(st, take(bt2, 0), eta,
                                      communicate=communicate, ring=ring,
                                      fl=flt(0))
            st, l2 = self._one_window(st, take(bt2, 1), eta,
                                      communicate=communicate, ring=ring,
                                      fl=flt(1))
            return st, jnp.concatenate([l1, l2], axis=0)  # [2I, K_loc]

        if faulty:
            def body(st, bt2, eta, fl2):
                return run_pair(st, bt2, eta, fl2)
        else:
            def body(st, bt2, eta):
                return run_pair(st, bt2, eta)

        st_specs = rules.shardmap_state_specs(state, self.mesh, self.policy)
        bt_specs = rules.shardmap_batch_specs(wb2, self.mesh, self.policy,
                                              self.ccfg.n_workers,
                                              worker_dim=2)
        from jax.sharding import PartitionSpec as P
        in_specs = (st_specs, bt_specs, P())
        if faulty:
            in_specs = in_specs + (self._fault_specs(lead, paired=True),)
        sm = _shard_map(body, mesh=self.mesh,
                        in_specs=in_specs,
                        out_specs=(st_specs, P(None, lead)),
                        check_vma=False)
        fn = jax.jit(sm, donate_argnums=self._donate)
        self._fns[key] = fn
        return fn

    def window_pair_step(self, state, wb2, eta, *, communicate: bool = True,
                         faults=None):
        fn = self.window_pair_fn(state, wb2, communicate=communicate)
        if self.ccfg.faults_enabled:
            if faults is None:
                raise ValueError(
                    "CoDAConfig enables fault injection; window_pair_step "
                    "needs the per-window fault vectors (leaves [2, K])")
            return fn(state, wb2, eta, faults)
        if faults is not None:
            raise ValueError(
                "fault vectors passed but CoDAConfig has fault injection "
                "disabled (set participation / straggler / crash knobs)")
        return fn(state, wb2, eta)

    # -- stage boundary ---------------------------------------------------
    def stage_fn(self, state, ab):
        key = self._key(("stage",), state, ab)
        if key in self._fns:
            return self._fns[key]
        mcfg, ccfg, wa = self.mcfg, self.ccfg, self.worker_axes

        from repro.core import objective as OBJ
        obj = OBJ.for_config(ccfg)

        def body(st, batch):
            upd = jax.vmap(
                lambda p, d, wb: coda.estimate_stage_duals(mcfg, ccfg, p, d,
                                                           wb))(
                st["params"], st["duals"], batch)        # {field: [K_loc]}
            upd = {k: jnp.mean(v) for k, v in upd.items()}
            if wa and upd:
                # ONE all-reduce of the stage-dual scalars (a tuple payload
                # of len(stage_fields) fp32 values — 4 bytes for AUC's α)
                upd = jax.lax.pmean(upd, wa)
            new = dict(st)
            new_duals = dict(st["duals"])
            for f, v in upd.items():
                new_duals[f] = jnp.full_like(st["duals"][f], v)
            new["duals"] = new_duals
            new["ref_params"] = st["params"]
            new["ref_duals"] = {f: st["duals"][f] for f in obj.prox_refs}
            return new

        st_specs = rules.shardmap_state_specs(state, self.mesh, self.policy)
        ab_specs = rules.shardmap_batch_specs(ab, self.mesh, self.policy,
                                              ccfg.n_workers, worker_dim=0)
        sm = _shard_map(body, mesh=self.mesh,
                        in_specs=(st_specs, ab_specs),
                        out_specs=st_specs, check_vma=False)
        fn = jax.jit(sm, donate_argnums=self._donate)
        self._fns[key] = fn
        return fn

    def stage_end(self, state, ab):
        return self.stage_fn(state, ab)(state, ab)
