"""Run the full dry-run sweep: all (arch × shape) on the single-pod mesh
(with L-delta + averaging probes for the roofline), then the multi-pod mesh
(full lowering only — the mesh-coherence proof; the roofline table is
single-pod per the spec).

  PYTHONPATH=src python scripts/sweep_dryrun.py [--skip-existing]

CPU only: every pair runs in its own jax child process on forced host
devices.  Keep it off a TPU: a chip belongs to one process at a time, so
the children would contend for it (fail or hang).
"""
import argparse
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ART = os.path.join(HERE, "benchmarks", "artifacts", "dryrun")

ARCHS = ["xlstm-350m", "stablelm-1.6b", "hymba-1.5b", "internvl2-2b",
         "chatglm3-6b", "seamless-m4t-medium", "qwen2.5-14b",
         "phi3-medium-14b", "dbrx-132b", "arctic-480b"]
SHAPES = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]

RUNNER = """
import sys
from repro.launch.dryrun import run_pair
run_pair(sys.argv[1], sys.argv[2], multi_pod=(sys.argv[3] == "1"))
"""


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    args = ap.parse_args()

    jobs = []
    for mp in ([True] if args.multi_pod_only else [False, True]):
        for arch in ARCHS:
            for shape in SHAPES:
                jobs.append((arch, shape, mp))

    env = {**os.environ, "PYTHONPATH": os.path.join(HERE, "src")}
    if "REPRO_MULTIPOD_FULL_ONLY" not in env:
        env["REPRO_MULTIPOD_FULL_ONLY"] = "1"
    for i, (arch, shape, mp) in enumerate(jobs):
        tag = f"{arch}__{shape}__{'pod2' if mp else 'pod1'}"
        path = os.path.join(ART, tag + ".json")
        if args.skip_existing and os.path.exists(path):
            print(f"[{i + 1}/{len(jobs)}] {tag}: exists, skip", flush=True)
            continue
        t0 = time.time()
        r = subprocess.run(
            [sys.executable, "-c", RUNNER, arch, shape, "1" if mp else "0"],
            env=env, cwd=HERE, capture_output=True, text=True, timeout=5400)
        out = (r.stdout + r.stderr).strip().splitlines()
        last = out[-1] if out else "?"
        print(f"[{i + 1}/{len(jobs)}] {last}  ({time.time() - t0:.0f}s)",
              flush=True)


if __name__ == "__main__":
    main()
