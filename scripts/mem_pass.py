"""Memory pass: re-lower each pod1 pair with ROLLED scans (the production
configuration — unrolling distorts XLA's live-range analysis) and update the
artifact's ``memory_rolled`` field with that module's memory_analysis().

For MoE archs every EVAL artifact (prefill/decode shapes — training always
uses capacity dispatch, so there is no before/after there) additionally
gets a ``moe_dispatch_bytes`` record: the per-layer dispatch-buffer bytes
the pass's token count implies under the padded capacity dispatch (before:
[E, C=T, d]) vs the sorted dropless dispatch (after: [T·k, d]) — see
models/moe.py and ``benchmarks/run.py --only moe_dispatch``.

Every TRAIN artifact additionally gets an ``optimizer_state_bytes``
record: per-worker accumulator bytes for each stateful registry optimizer
(core/optimizer.py) in fp32 vs bf16 storage, computed analytically via
``jax.eval_shape`` — the local-memory side of the optimizer seam (the
wire side is pinned by the audit's window-payload rule).

  PYTHONPATH=src python scripts/mem_pass.py [--arch X --shape Y]

CPU only: it lowers for 512 forced host devices, one jax child process per
pair, and its parent also imports jax.  Keep it off a TPU: a chip belongs
to one process, so a parent holding it leaves the children to fail or hang.
"""
import argparse
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ART = os.path.join(HERE, "benchmarks", "artifacts", "dryrun")
sys.path.insert(0, os.path.join(HERE, "src"))

RUNNER = """
import os, json, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
from repro import flags
from repro.launch import dryrun as DR
flags.DRYRUN_UNROLL = False  # rolled: the production module
arch, shape = sys.argv[1], sys.argv[2]
from repro.launch import mesh as MESH
mesh = MESH.make_production_mesh(multi_pod=False)
lowered, meta = DR.build_lowering(arch, shape, mesh, variant="full")
compiled = lowered.compile()
mem = compiled.memory_analysis()
from repro.analysis import hlo as H
coll = H.collective_bytes(compiled.as_text())
ca = compiled.cost_analysis() or {}
rec = {
    "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
    "output_bytes": getattr(mem, "output_size_in_bytes", None),
    "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
    "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
    "rolled_coll_bytes": coll["total_bytes"],
    "rolled_flops": float(ca.get("flops", 0.0)),
}
print("MEMJSON " + json.dumps(rec))
"""


def moe_dispatch_record(arch: str, shape_name: str):
    """Analytic before/after dispatch-buffer bytes for one (arch, shape).
    Returns None for non-MoE archs and for train shapes (training always
    uses capacity dispatch — the sorted path is eval/decode-only, so a
    before/after there would be fiction)."""
    from repro.configs import SHAPES, get_config
    from repro.models import moe
    cfg = get_config(arch)
    spec = SHAPES[shape_name]
    if cfg.moe is None or spec.kind == "train":
        return None
    T = moe.tokens_per_forward(spec)
    cap = moe.dispatch_buffer_bytes(cfg, T, mode="capacity", dtype="bfloat16")
    srt = moe.dispatch_buffer_bytes(cfg, T, mode="sorted", dtype="bfloat16")
    return {"tokens": T,
            "capacity_bytes": cap,       # before: [E, C=T, d] per layer
            "sorted_bytes": srt,         # after:  [T·k, d] per layer
            "ratio": cap / srt}


def optimizer_state_record(arch: str, shape_name: str):
    """Analytic per-worker optimizer-state bytes for one (arch, shape):
    every stateful registry optimizer × {fp32, bf16} accumulator storage,
    from ``jax.eval_shape``-traced state (no buffers materialized).  The
    state is strictly LOCAL — it never joins the window payload — so these
    bytes are pure per-worker HBM, and the fp32/bf16 ratio is the memory
    the stochastic-rounded buffers buy back.  None for non-train shapes
    (eval/decode lowering has no optimizer)."""
    import jax
    import jax.numpy as jnp

    from repro.configs import SHAPES, get_config
    from repro.core import coda
    spec = SHAPES[shape_name]
    if spec.kind != "train":
        return None
    mcfg = get_config(arch)
    out = {}
    for optname in ("momentum", "sm3", "shampoo_blocked"):
        per_dt = {}
        for dtn, dt in (("fp32", jnp.float32), ("bf16", jnp.bfloat16)):
            ccfg = coda.CoDAConfig(n_workers=8, optimizer=optname,
                                   opt_dtype=dt)
            sts = jax.eval_shape(
                lambda k, c=ccfg: coda.init_state(k, mcfg, c),
                jax.random.PRNGKey(0))
            per_dt[dtn] = coda.opt_state_bytes(sts)
        per_dt["bf16_reduction"] = round(
            per_dt["fp32"] / max(1, per_dt["bf16"]), 2)
        out[optname] = per_dt
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    args = ap.parse_args()
    env = {**os.environ, "PYTHONPATH": os.path.join(HERE, "src")}
    for f in sorted(os.listdir(ART)):
        if not f.endswith("__pod1.json"):
            continue
        rec = json.load(open(os.path.join(ART, f)))
        if rec.get("status") != "ok":
            continue
        arch, shape = rec["arch"], rec["shape"]
        if args.arch and arch != args.arch:
            continue
        if args.shape and shape != args.shape:
            continue
        if "moe_dispatch_bytes" not in rec:
            md = moe_dispatch_record(arch, shape)
            if md is not None:
                rec["moe_dispatch_bytes"] = md
                json.dump(rec, open(os.path.join(ART, f), "w"), indent=1)
                print(f"{f}: moe dispatch buffer {md['ratio']:.0f}x "
                      f"(capacity/sorted)", flush=True)
        if "optimizer_state_bytes" not in rec:
            try:
                od = optimizer_state_record(arch, shape)
            except Exception as e:          # never block the memory pass
                print(f"{f}: optimizer record failed: {e}", flush=True)
                od = None
            if od is not None:
                rec["optimizer_state_bytes"] = od
                json.dump(rec, open(os.path.join(ART, f), "w"), indent=1)
                print(f"{f}: optimizer state/worker " + " ".join(
                    f"{o}={d['bf16']:,}B(bf16,{d['bf16_reduction']}x)"
                    for o, d in od.items()), flush=True)
        if "memory_rolled" in rec:
            continue
        # decode lowerings have no scans — rolled == unrolled already
        if shape in ("decode_32k", "long_500k") and not args.shape:
            continue
        r = subprocess.run([sys.executable, "-c", RUNNER, arch, shape],
                           env=env, cwd=HERE, capture_output=True, text=True,
                           timeout=3000)
        out = [l for l in r.stdout.splitlines() if l.startswith("MEMJSON ")]
        if out:
            rec["memory_rolled"] = json.loads(out[-1][8:])
            json.dump(rec, open(os.path.join(ART, f), "w"), indent=1)
            tb = rec["memory_rolled"].get("temp_bytes")
            print(f"{f}: temp={tb and tb / 2**30:.1f}GiB", flush=True)
        else:
            print(f"{f}: FAILED {r.stderr[-200:]}", flush=True)


if __name__ == "__main__":
    main()
