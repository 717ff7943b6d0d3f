"""CoDA training launcher.

CPU-scale end-to-end run (reduced configs) or the production mesh layout.

Executor selection (--executor):
  * vmap       — single-device oracle: the K-worker axis is a batched array
                 axis; exact semantics, nothing crosses a wire.
  * shard_map  — production path (core/coda_sharded.py): workers laid over
                 real mesh devices, I local steps collective-free, one
                 bucketed all-reduce per window.  On a CPU host pass
                 --force-host-devices N to split the host into N XLA
                 devices (the flag must take effect before jax initialises,
                 which is why it is a CLI arg and not ambient config).

Algorithm selection (--algorithm):
  * coda     — the paper's algorithm (assumes homogeneous shards).
  * codasca  — control-variate corrected CoDA (core/codasca.py) for
               heterogeneous shards; same ONE all-reduce per window, 2x the
               payload.  Pair with --dirichlet-alpha to make the shards
               actually heterogeneous: Dirichlet(α) label skew, small α =
               extreme skew, unset/inf = the paper's IID split.

Objective selection (--objective, core/objective.py registry):
  * auc      — the paper's min-max AUC (duals a, b, α).
  * pauc_dro — one-way partial AUC at FPR ≤ --pauc-beta as a KL-DRO
               min-max: negatives are softmax-reweighted by hardness with
               the DRO temperature riding the dual state.  The run summary
               reports pAUC@β next to full AUC.
Both ship their dual tree in the same one-bucket window all-reduce; the
payload accounting adapts to the tree automatically.

--server-momentum β applies the CODASCA-style server momentum buffer to
every window's averaged iterate (replicated server state, zero extra wire
bytes; 0 = off).

Metric reporting (shared flags with launch/serve.py via
repro.metrics.report): --metrics exact evaluates the held-out test split at
every --metric-interval windows through the exact Metric backend;
--metrics sketch turns on the in-training streaming sketch
(CoDAConfig.stream_bins = --metric-bins): every local step histograms the
scores the loss already computed, the per-window merge rides the existing
window all-reduce as 2·bins·4 extra fp32 bytes, and the report line shows
the training-stream AUC with its resolution bound.

Fault tolerance (--participation / --straggler-prob / --max-staleness /
--fault-seed): a seed-deterministic FaultPlan (core/faults.py) drops a
fraction of per-window contributions and delays stragglers; the window
all-reduce switches to the masked participant mean (still ONE collective,
payload + a tiny weight lane).  --ckpt-every N + --ckpt-dir save
crash-recovery checkpoints at window boundaries; --resume restarts
bitwise-identically to the uninterrupted run.

Overlapped averaging (--overlap, shard_map only): the window all-reduce is
rescheduled as C = --overlap-chunks ppermute ring chains per dtype bucket
inside a fused two-window step, so the first window's wire time hides under
the second window's local compute.  Same mean, same logical comm bytes —
the run summary splits them into overlapped vs exposed.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b --smoke \
      --workers 4 --stages 2 --t0 30 --interval 8
  PYTHONPATH=src python -m repro.launch.train --arch mlp --workers 8 \
      --executor shard_map --force-host-devices 8 --overlap \
      --overlap-chunks 4 --stages 2 --interval 4
  PYTHONPATH=src python -m repro.launch.train --arch mlp --workers 8 \
      --stages 3 --t0 100 --interval 16 --p-pos 0.71 \
      --executor shard_map --force-host-devices 8 --compress int8
  PYTHONPATH=src python -m repro.launch.train --arch mlp --workers 8 \
      --algorithm codasca --dirichlet-alpha 0.1 --stages 3 --interval 16
"""
from __future__ import annotations

import argparse
import os
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint
from repro.configs import get_config, get_smoke_config
from repro.configs.base import mlp_config
from repro.core import coda, objective, optimizer, schedules
from repro.data import DataConfig, ShardedDataset
from repro.launch import mesh as mesh_mod
from repro.metrics import report as metric_report
from repro.metrics import streaming

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


def data_config_for(mcfg, p_pos: float) -> DataConfig:
    if mcfg.family == "mlp":
        return DataConfig(kind="features", p_pos=p_pos, n_features=mcfg.n_features)
    if mcfg.family == "cnn":
        return DataConfig(kind="images", p_pos=p_pos, image_hw=32)
    return DataConfig(kind="tokens", p_pos=p_pos, vocab_size=mcfg.vocab_size,
                      seq_len=64, d_model=mcfg.d_model)


def make_batch_adapters(mcfg, ds: ShardedDataset, key):
    """Wrap the dataset so modality stubs (patches/frames) are attached."""

    def adapt(b):
        if mcfg.family == "vlm":
            lead = b["tokens"].shape[:-1]
            b = dict(b)
            b["patches"] = jax.random.normal(
                key, lead + (mcfg.n_patches, mcfg.d_model))
            b["tokens"] = b["tokens"][..., :max(1, b["tokens"].shape[-1] - mcfg.n_patches)]
        elif mcfg.family == "audio":
            lead = b["tokens"].shape[:-1]
            S = b["tokens"].shape[-1]
            b = dict(b)
            b["frames"] = jax.random.normal(key, lead + (S, mcfg.d_model))
            b["tokens"] = b["tokens"][..., :max(1, S // mcfg.decoder_fraction)]
        return b

    return adapt


def use_compile_cache(root: pathlib.Path = REPO_ROOT) -> None:
    """Keep JAX's persistent compilation cache at ``<root>/.jax_cache``.

    The path is fixed because it is part of the cache key: a directory that
    moves never hits.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
    it itself and no path is set here.  Entry points call this; importing
    ``repro`` never does."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))


def device_summary() -> dict:
    """The platform, kind and count of the devices JAX runs on."""
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mlp")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--stages", type=int, default=3)
    ap.add_argument("--t0", type=int, default=60)
    ap.add_argument("--eta0", type=float, default=0.5)
    ap.add_argument("--interval", type=int, default=8, help="I (0 = Thm-1 rule)")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--p-pos", type=float, default=0.71)
    ap.add_argument("--n-data", type=int, default=8192)
    ap.add_argument("--algorithm", choices=["coda", "codasca"], default="coda",
                    help="codasca = control-variate corrected local steps "
                         "for heterogeneous (non-IID) shards")
    ap.add_argument("--objective", choices=list(objective.names()),
                    default="auc",
                    help="which min-max objective to solve "
                         "(core/objective.py registry)")
    ap.add_argument("--pauc-beta", type=float, default=0.3,
                    help="FPR budget β for --objective pauc_dro")
    ap.add_argument("--server-momentum", type=float, default=0.0,
                    help="β for server momentum on the averaged iterate "
                         "(0 = off; the buffer stays server-side, no extra "
                         "wire bytes)")
    ap.add_argument("--optimizer", choices=list(optimizer.names()),
                    default="sgd",
                    help="local primal optimizer (core/optimizer.py "
                         "registry); preconditioning is strictly LOCAL — "
                         "the window all-reduce still carries only the "
                         "model payload, never optimizer state")
    ap.add_argument("--opt-dtype", choices=["fp32", "bf16"], default="fp32",
                    help="storage dtype for optimizer accumulators; bf16 "
                         "halves optimizer-state bytes (fp32 master math "
                         "in-kernel, stochastic-rounded stores)")
    ap.add_argument("--opt-beta", type=float, default=0.9,
                    help="momentum coefficient (--optimizer momentum)")
    ap.add_argument("--opt-eps", type=float, default=1e-6,
                    help="preconditioner damping (sm3 / shampoo_blocked)")
    ap.add_argument("--shampoo-block", type=int, default=32,
                    help="block size b for shampoo_blocked's per-block "
                         "[b, b] second-moment statistics")
    ap.add_argument("--precond-every", type=int, default=1,
                    help="recompute the shampoo inverse-root preconditioner "
                         "every N local steps (stale preconditioner "
                         "in between — cheaper, usually harmless)")
    ap.add_argument("--dirichlet-alpha", type=float, default=float("inf"),
                    help="Dirichlet(α) label-skew across the K shards "
                         "(inf = IID even split, the paper's setting)")
    ap.add_argument("--participation", type=float, default=1.0,
                    help="per-window probability a worker's contribution "
                         "makes the merge (< 1 turns on the fault-injection "
                         "harness: masked participant-mean averaging, same "
                         "ONE all-reduce per window)")
    ap.add_argument("--straggler-prob", type=float, default=0.0,
                    help="per-window probability a worker starts straggling "
                         "(its contributions arrive --straggler-windows "
                         "windows late)")
    ap.add_argument("--straggler-windows", type=int, default=1,
                    help="how many windows a straggler's contribution lags")
    ap.add_argument("--max-staleness", type=int, default=0,
                    help="merge straggler contributions up to this many "
                         "windows late (staleness-discounted weight); later "
                         "arrivals are dropped and the worker re-synced")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the deterministic fault schedule "
                         "(core/faults.FaultPlan — replayable)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="with --ckpt-dir: save state + loop counters every "
                         "N windows (crash-recovery checkpoints; resume "
                         "with --resume)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest checkpoint in --ckpt-dir "
                         "(bitwise-identical to the uninterrupted run)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--executor", choices=["vmap", "shard_map"],
                    default="vmap",
                    help="vmap = single-device oracle; shard_map = workers "
                         "on real mesh devices with one all-reduce/window")
    ap.add_argument("--policy", choices=["replica", "fsdp"], default="replica",
                    help="worker placement: replica = workers over the data "
                         "axis; fsdp = workers over the pod axis only")
    ap.add_argument("--compress", choices=["", "int8"], default="",
                    help="int8 = compressed averaging: only the int8 payload "
                         "+ per-tensor fp32 scales cross the wire")
    ap.add_argument("--overlap", action="store_true",
                    help="overlap the window averaging with the next "
                         "window's compute: the sharded executor fuses "
                         "window PAIRS and lowers each averaging as chunked "
                         "ppermute rings instead of one blocking all-reduce "
                         "(requires --executor shard_map; same mean, same "
                         "comm bytes, first-of-pair latency hidden)")
    ap.add_argument("--overlap-chunks", type=int, default=4,
                    help="ring chains per dtype bucket under --overlap "
                         "(more chunks = finer overlap granularity, more "
                         "ppermute hops)")
    ap.add_argument("--force-host-devices", type=int, default=0,
                    help="split the CPU host into N XLA devices (needed for "
                         "--executor shard_map on CPU; must be a fresh "
                         "process — jax locks the device count on first use)")
    ap.add_argument("--multi-pod", action="store_true",
                    help="use the 3-axis (pod, data, model) mesh layout")
    metric_report.add_metric_args(ap)
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)
    if args.force_host_devices:
        mesh_mod.force_host_device_count(args.force_host_devices)
    use_compile_cache()
    run(args)


def run(args) -> dict:
    """Train as the parsed ``args`` say and print the run summary.  Returns
    the ``FitResult`` (``fit``), the test AUC (``auc``) and scores
    (``scores``), the mesh (None for the vmap executor), the model and CoDA
    configs and the stage list."""
    print("device:", device_summary())

    if args.arch == "mlp":
        mcfg = mlp_config()
    elif args.smoke:
        mcfg = get_smoke_config(args.arch)
    else:
        mcfg = get_config(args.arch)

    key = jax.random.PRNGKey(args.seed)
    dcfg = data_config_for(mcfg, args.p_pos)
    ds = ShardedDataset(key, dcfg, args.n_data, args.workers,
                        target_p=args.p_pos,
                        dirichlet_alpha=args.dirichlet_alpha)
    adapt = make_batch_adapters(mcfg, ds, key)
    print(f"dataset: n={ds.n} p_pos={ds.p_pos:.3f} workers={args.workers}")
    if np.isfinite(args.dirichlet_alpha):
        pp = np.array(ds.shard_p_pos)
        print(f"non-IID shards (Dirichlet α={args.dirichlet_alpha:g}): "
              f"sizes={ds.shard_sizes} shard p_pos "
              f"[{pp.min():.2f}, {pp.max():.2f}] (std {pp.std():.3f})")

    if args.overlap and args.executor != "shard_map":
        raise SystemExit("--overlap needs --executor shard_map (the vmap "
                         "oracle has no wire to overlap)")
    ccfg = coda.CoDAConfig(n_workers=args.workers, p_pos=ds.p_pos,
                           avg_compress=args.compress,
                           algorithm=args.algorithm,
                           objective=args.objective,
                           pauc_beta=args.pauc_beta,
                           server_momentum=args.server_momentum,
                           overlap_chunks=args.overlap_chunks
                           if args.overlap else 0,
                           stream_bins=args.metric_bins
                           if args.metrics == "sketch" else 0,
                           participation=args.participation,
                           straggler_prob=args.straggler_prob,
                           straggler_windows=args.straggler_windows,
                           max_staleness=args.max_staleness,
                           fault_seed=args.fault_seed,
                           optimizer=args.optimizer,
                           opt_dtype=jnp.bfloat16
                           if args.opt_dtype == "bf16" else jnp.float32,
                           opt_beta=args.opt_beta,
                           opt_eps=args.opt_eps,
                           shampoo_block=args.shampoo_block,
                           precond_every=args.precond_every)
    if args.optimizer != "sgd":
        sts = jax.eval_shape(lambda k: coda.init_state(k, mcfg, ccfg), key)
        print(f"optimizer: {args.optimizer} ({args.opt_dtype}) "
              f"state={coda.opt_state_bytes(sts):,} B/worker "
              f"(local only — never on the wire)")
    if ccfg.faults_enabled:
        print(f"fault injection: participation={args.participation:g} "
              f"straggler_prob={args.straggler_prob:g} "
              f"(lag {args.straggler_windows}, max_staleness "
              f"{args.max_staleness}) seed={args.fault_seed}")
    sched = schedules.ScheduleConfig(n_workers=args.workers, eta0=args.eta0,
                                     T0=args.t0, I0=args.interval,
                                     p_pos=ds.p_pos)

    mesh = None
    if args.executor == "shard_map":
        mesh = mesh_mod.make_worker_mesh(multi_pod=args.multi_pod)
        print(f"mesh: {dict(mesh.shape)} policy={args.policy} "
              f"devices={len(mesh.devices.flat)}")

    test = adapt(ds.full(2048))
    obj = objective.for_config(ccfg)
    from repro.models import model as M
    score = jax.jit(lambda p, x: M.score(mcfg, p, x)[0])

    def test_scores(state, chunk: int = 256):
        # jitted and chunked: an eager full-width forward over the whole
        # split would hold every layer's activations for 2048 inputs at once
        params0 = jax.tree_util.tree_map(lambda x: x[0], state["params"])
        inputs = {k: v for k, v in test.items() if k != "labels"}
        n = len(test["labels"])
        return jnp.concatenate([
            score(params0, jax.tree_util.tree_map(lambda v: v[i:i + chunk],
                                                  inputs))
            for i in range(0, n, chunk)])

    # the eval hook reports through the shared metric plumbing: sketch mode
    # lifts the in-training streaming accumulator (state["sk_acc"], merged on
    # the window wire) to the host; exact mode scores the held-out split
    met = obj.metric(args.metrics, bins=args.metric_bins,
                     lo=ccfg.stream_range[0], hi=ccfg.stream_range[1]) \
        if args.metrics == "sketch" else obj.metric("exact")
    rep = metric_report.IntervalReporter(met, interval=args.metric_interval,
                                         label="train")
    n_evals = [0]

    def eval_fn(state) -> float:
        n_evals[0] += 1
        if args.metrics == "sketch":
            sk = streaming.sketch_from_rows(state["sk_acc"],
                                            *ccfg.stream_range)
            out = rep.report(f"eval {n_evals[0]}", sk, n_seen=int(sk.count))
            if "sk_loc" in state:
                # per-worker AUC skew off the local (never-averaged) sketch
                # lanes — zero extra wire bytes
                print(metric_report.worker_skew_line(
                    "train", f"eval {n_evals[0]}", met, state["sk_loc"],
                    *ccfg.stream_range))
            return out
        st = met.update(met.init(), test_scores(state), test["labels"])
        return rep.report(f"eval {n_evals[0]}", st,
                          n_seen=int(np.asarray(test["labels"]).size))

    t0 = time.time()
    res = coda.fit(
        key, mcfg, ccfg, sched, args.stages,
        sample_window=lambda k, i: adapt(ds.sample_window(k, i, args.batch)),
        sample_alpha_batch=lambda k, m: adapt(ds.sample_alpha_batch(k, m)),
        eval_every=args.metric_interval,
        eval_fn=eval_fn if args.metric_interval else None,
        executor=args.executor, mesh=mesh, policy=args.policy,
        ckpt_dir=args.ckpt_dir if args.ckpt_every else "",
        ckpt_every=args.ckpt_every, resume=args.resume)
    dt = time.time() - t0
    h_test = test_scores(res.state)
    auc = streaming.make_metric("auc", "exact").compute(h_test, test["labels"])
    extra = ""
    if obj.metric_name != "auc":
        m = obj.metric("exact").compute(h_test, test["labels"])
        extra = f", test {obj.metric_name}@{args.pauc_beta:g}={m:.4f}"
    print(f"done: {res.iterations} iters, {res.comm_rounds} comm rounds, "
          f"{dt:.1f}s, test AUC={auc:.4f}{extra}")
    if args.metrics == "sketch":
        sk = streaming.sketch_from_rows(res.state["sk_acc"],
                                        *ccfg.stream_range)
        rep.report("final train-stream", sk, n_seen=int(sk.count))
        if "sk_loc" in res.state:
            print(metric_report.worker_skew_line(
                "train", "final", met, res.state["sk_loc"],
                *ccfg.stream_range))
    compress = args.compress or None
    total = coda.comm_bytes(schedules.stages(sched, args.stages), res.state,
                            compress,
                            stage_bytes=coda.stage_payload_bytes(ccfg))
    print(f"bytes/round/worker={coda.window_payload_bytes(res.state, compress):,} "
          f"(schedule total {total:,})")
    if args.overlap:
        print(f"overlap: {res.overlapped_bytes:,} bytes hidden under "
              f"next-window compute, {res.exposed_bytes:,} exposed "
              f"(chunks={args.overlap_chunks})")
    if args.ckpt_dir and not args.ckpt_every:
        # final-state export only; --ckpt-every owns the directory for the
        # crash-recovery window checkpoints (their metadata carries the
        # loop counters --resume restarts from)
        path = checkpoint.save(args.ckpt_dir, res.iterations, res.state,
                               {"auc": auc, "arch": mcfg.name})
        print("checkpoint:", path)
    return {"fit": res, "auc": auc, "scores": h_test, "mesh": mesh,
            "mcfg": mcfg, "ccfg": ccfg,
            "stages": schedules.stages(sched, args.stages)}


if __name__ == "__main__":
    main()
