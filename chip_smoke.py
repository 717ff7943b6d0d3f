"""Smoke run of CoDA training on TPU chips: the quickest proof that the
training path still starts on the chip and computes the right thing.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # the four-chip path only

One chip: ResNet-50 at its full widths (``configs/resnet50.CONFIG``) trains
through ``launch/train.run`` with the shard_map executor on a one-device
mesh: K = 4 CoDA workers, 32 CIFAR-shaped synthetic images per worker and
step, one stage of T = 16 local steps at I = 4 (four windows), then the
stage boundary.  Then one window runs twice from one state and batch, once
with the Pallas kernels (``impl="auto"``) and once with their jnp
references (``impl="ref"``); the two must agree.

``--chips 4``: the same training with one worker per chip on a (4, 1) mesh,
against the vmap oracle on one chip from the same key and batches.  The
state must span the four chips and the compiled window must be one
all-reduce per dtype bucket.

Weights and data are made from ``--seed``.  The numbers printed before the
last line are smoke readings, not benchmark metrics.  The last line is one
JSON object naming the device; it is printed only when every check passed.
The script exits non-zero, printing no such line, when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import jax
import jax.monitoring
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# launch/train.py's arguments for the run: full-width ResNet-50, K = 4
# workers, per-worker batch 32, one stage of four I = 4 windows
TRAIN_ARGS = ["--arch", "resnet50", "--workers", "4", "--batch", "32",
              "--stages", "1", "--t0", "16", "--interval", "4"]

# Agreement bound for two programs that compute the same training math:
# max |got - want| over a tree's leaves, divided by max |want - start| (the
# size of the update the run made).  The programs differ in summation order
# (blocked kernel sums vs XLA reductions, per-chip vs vmapped programs) and
# in XLA's fusion choices; on the chip fp32 convolutions run in bf16 passes
# at default precision, so an ulp-level difference in a weight can flip a
# bf16 rounding and show up in the next step's gradient.  That stays well
# under 2% of the update.  A wrong kernel partial, a dropped worker or a
# mis-averaged bucket moves the result by the order of the update itself.
REL_TOL = 2e-2


def _max_abs(tree) -> float:
    return max((float(jnp.max(jnp.abs(x.astype(jnp.float32))))
                for x in jax.tree_util.tree_leaves(tree)), default=0.0)


def _rel_err(got, want, start) -> float:
    """max |got - want| relative to max |want - start| over a tree."""
    diff = jax.tree_util.tree_map(lambda a, b: a - b, got, want)
    step = jax.tree_util.tree_map(lambda a, b: a - b, want, start)
    return _max_abs(diff) / max(_max_abs(step), 1e-30)


def _check_agree(label: str, got, want, start) -> None:
    for part in ("params", "duals"):
        err = _rel_err(got[part], want[part], start[part])
        print(f"{label} {part}: max diff / max update = {err:.3e} "
              f"(limit {REL_TOL:g})")
        if not err <= REL_TOL:
            raise SystemExit(f"chip_smoke: {label} {part} disagree "
                             f"({err:.3e} > {REL_TOL:g})")


class CompileClock:
    """Sums the backend compile seconds JAX reports while it is open."""

    def __init__(self):
        self.seconds, self.count = 0.0, 0

    def __call__(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self)


def _train(train, seed: int, executor: str) -> dict:
    args = train.build_parser().parse_args(
        TRAIN_ARGS + ["--executor", executor, "--seed", str(seed)])
    with CompileClock() as clock:
        out = train.run(args)
        jax.block_until_ready(out["fit"].state)
    losses = [loss for _, _, loss in out["fit"].history]
    h = out["scores"]
    print(f"smoke reading [{executor}]: backend compile {clock.seconds:.1f} s "
          f"over {clock.count} programs; per-window losses {losses}; "
          f"test AUC {out['auc']:.4f}, test scores in "
          f"[{float(h.min())!r}, {float(h.max())!r}]")
    if not all(map(math.isfinite, losses)):
        raise SystemExit(f"chip_smoke: non-finite window loss {losses}")
    return out


def _peak_bytes() -> None:
    for d in jax.devices():
        stats = d.memory_stats() or {}
        print(f"smoke reading: {d} peak_bytes_in_use="
              f"{stats.get('peak_bytes_in_use', 'not reported')}")


def one_chip(train, seed: int) -> None:
    """ResNet-50 training on one chip, then the kernels against their
    references inside one compiled window."""
    from repro.core import coda
    from repro.data import DataConfig, sample_online

    out = _train(train, seed, "shard_map")
    _peak_bytes()
    mcfg, ccfg, mesh = out["mcfg"], out["ccfg"], out["mesh"]
    stage = out["stages"][0]
    del out

    key = jax.random.PRNGKey(seed + 1)
    wb = sample_online(key, DataConfig(kind="images", p_pos=ccfg.p_pos),
                       (stage.I, ccfg.n_workers, 32))
    eta = jnp.float32(stage.eta)
    st0 = coda.init_state(key, mcfg, ccfg)
    got = {}
    for impl in ("auto", "ref"):
        cfg = coda.CoDAConfig(n_workers=ccfg.n_workers, p_pos=ccfg.p_pos,
                              impl=impl)
        exe = coda.make_executor(mcfg, cfg, "shard_map", mesh=mesh,
                                 donate=False)
        st = exe.place(st0)
        t = time.perf_counter()
        compiled = exe.window_fn(st, wb).lower(st, wb, eta).compile()
        kernels = compiled.as_text().count("tpu_custom_call")
        print(f"smoke reading [impl={impl}]: window compile "
              f"{time.perf_counter() - t:.1f} s, {kernels} Mosaic kernel "
              "calls in the compiled window")
        if (kernels > 0) != (impl == "auto"):
            raise SystemExit(f"chip_smoke: impl={impl} window has {kernels} "
                             "tpu_custom_call ops")
        got[impl], losses = compiled(st, wb, eta)
        jax.block_until_ready(got[impl])
        if not bool(jnp.all(jnp.isfinite(losses))):
            raise SystemExit(f"chip_smoke: impl={impl} non-finite losses")
    _check_agree("impl=auto vs impl=ref", got["auto"], got["ref"], st0)


def four_chips(train, seed: int) -> None:
    """The shard_map executor with one worker per chip against the vmap
    oracle on one chip."""
    from repro.analysis import audit
    from repro.core import coda

    # Both sides run at full fp32 matmul precision.  At the chip's default
    # (one bf16 pass per fp32 conv) the batched vmap program and the
    # per-chip programs round differently, and sixteen steps at eta0 = 0.5
    # can grow that past the bound; at "highest" they differ only in
    # summation order, so a disagreement means a wrong collective or a
    # misplaced worker.
    with jax.default_matmul_precision("highest"):
        sharded = _train(train, seed, "shard_map")
        oracle = _train(train, seed, "vmap")
    st = sharded["fit"].state
    start = coda.init_state(jax.random.PRNGKey(seed), sharded["mcfg"],
                            sharded["ccfg"])
    _check_agree("shard_map (4 chips) vs vmap (1 chip)", st,
                 oracle["fit"].state, start)
    _peak_bytes()

    for path, leaf in jax.tree_util.tree_flatten_with_path(st["params"])[0]:
        devs = {s.device for s in leaf.addressable_shards}
        rows = {s.data.shape[0] for s in leaf.addressable_shards}
        if len(devs) != 4 or rows != {1}:
            raise SystemExit(
                f"chip_smoke: params{jax.tree_util.keystr(path)} is not one "
                f"worker per chip: {len(devs)} devices, rows {rows}")
    print("state: every params leaf holds one worker on each of 4 devices")

    mcfg, ccfg, stage = sharded["mcfg"], sharded["ccfg"], sharded["stages"][0]
    exe = coda.make_executor(mcfg, ccfg, "shard_map", mesh=sharded["mesh"],
                             donate=False)
    wb = {"images": jax.ShapeDtypeStruct(
              (stage.I, ccfg.n_workers, 32, 32 * 32, 3), "float32"),
          "labels": jax.ShapeDtypeStruct((stage.I, ccfg.n_workers, 32),
                                         "float32")}
    sts = jax.eval_shape(lambda s: s, st)
    txt = exe.window_fn(sts, wb).lower(
        sts, wb, jax.ShapeDtypeStruct((), "float32")).compile().as_text()
    ops = audit.assert_window_payload(txt, coda.window_payload_bytes(st),
                                      by_dtype=coda.window_payload_by_dtype(st))
    print(f"window collectives: {[(o['op'], o['by_dtype']) for o in ops]}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip shard_map path and the "
                         "one-chip vmap oracle it is compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    try:
        from repro.launch import train
    except ModuleNotFoundError as e:
        raise SystemExit(f"chip_smoke: run it from the repository ({e})")
    dev = train.device_summary()
    print("device:", dev)
    if dev["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: JAX found platform "
                         f"{dev['platform']!r}, not 'tpu'; nothing was run")
    if dev["count"] < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} devices, JAX found {dev['count']}")
    train.use_compile_cache()
    print("compile cache:", jax.config.jax_compilation_cache_dir)

    if args.chips == 4:
        four_chips(train, args.seed)
    else:
        one_chip(train, args.seed)
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
