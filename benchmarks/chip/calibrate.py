"""Readings that the limits in ``checks/<cell>.json`` are set from, taken on
the chip at the cell's own size, in one process so that every program
compiles once.  Not part of a benchmark run.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 1,2,...  --control-seeds 7,8,9  --fault-seeds 7,8,9 \
        --out calibrate.json
    python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 5 \
        --seconds 1 --trace-sample trace.json.gz

* ``--seeds``: sound runs of the program (set-up, one timed window, the
  reference); the largest reading of each number is its lower reading.
* ``--control-seeds``: the reference in bfloat16 put in the program's
  place; the smallest reading is a number's upper reading.
* ``--fault-seeds``: faults planted in the reference put in the program's
  place: half of every batch left out (the mean over the rest), and, where
  the cell has more than one worker, the average over workers left out.
* ``--look-precision``: also compare each sound run with the reference
  in float32 at this matmul precision (``default``: the program's), to see
  how much of each gap the precision accounts for.
* ``--trace-sample``: print the traced window's planes, lines and busiest
  operations, and write its first window in ``trace_reduce.dump``'s compact
  form (what ``testdata/`` holds).
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                   "src")]


def _seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def window_batches(cell, seed: int) -> list:
    """The host batches of the run's first windows, as the sampler draws
    them for ``seed``."""
    import generator
    import harness

    ds = generator.Dataset(seed, cell.model.inputs(cell.cfg), cell.traffic,
                           cell.K)
    s = generator.Sampler(ds, cell.B, seed, None)
    return [s.host_window(cell.I) for _ in range(harness.OBSERVED_WINDOWS)]


def describe_trace(path: str) -> None:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: lines {[l.name for l in lines]}")
        for line in lines:
            evs = list(line.events)
            tot: dict[str, float] = {}
            for e in evs:
                tot[e.name] = tot.get(e.name, 0.0) + e.duration_ns
            top = sorted(tot.items(), key=lambda kv: -kv[1])[:12]
            print(f"  line {line.name!r}: {len(evs)} events; top {top}")
            for e in evs[:2]:
                print(f"    e {e.name!r} start {e.start_ns} dur "
                      f"{e.duration_ns} stats {list(e.stats)[:8]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--trace-sample", default="")
    ap.add_argument("--look-precision", default="")
    ap.add_argument("--out", default="")
    ap.add_argument("--eta0", type=float, default=0.0,
                    help="another step size than the configuration's, to "
                         "read the scores at the window's close")
    args = ap.parse_args(argv)

    import harness

    cell = harness.Cell(args.workload)
    if args.eta0:
        cell.cfg["coda"] = dict(cell.cfg["coda"], eta0=args.eta0)
    harness.configure_cache()
    problem = harness.device_check(cell.chips)
    if problem:
        print(f"calibrate.py: {problem}", file=sys.stderr)
        return 3
    out = {"workload": args.workload, "program": [], "control": [],
           "faults": []}
    import jax

    import generator
    import reference
    import trace_reduce

    names = reference.leaf_names(jax.eval_shape(
        lambda k: {"params": cell.model.make_weights(k, cell.cfg),
                   "duals": {f: 0.0 for f in ("a", "b", "alpha")}},
        generator.jax_key(0, 4)))

    def dump():
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(out, f, indent=1)

    for seed in _seeds(args.seeds):
        t = time.perf_counter()
        tr = harness.train(cell, seed, args.seconds,
                           trace=bool(args.trace_sample), score_range=True)
        t_ref = time.perf_counter()
        want = harness.reference(cell, seed, tr["batches"])
        nums = harness.check_numbers(cell, tr["readings"], want,
                                     tr["compiles"], every=True)
        look = {k: reference.worst_leaves(tr["readings"], want, k, names)
                for k in ("change1", "change3")}
        row = {"worst_leaves": look, "seed": seed, "numbers": {k: v["value"]
                                         for k, v in nums.items()},
               "setup_s": tr["setup_s"], "reference_s":
               time.perf_counter() - t_ref, "run_s": time.perf_counter() - t,
               "windows": tr["windows"], "window_s": tr["seconds"],
               "peak": tr["peak"], "setup_parts": tr["setup_parts"],
               "scores_at_close": tr["scores"],
               "loss": tr["readings"]["loss"].tolist()}
        if args.look_precision:
            alt = harness.reference(cell, seed, tr["batches"],
                                    precision=args.look_precision)
            row["look"] = {
                "precision": args.look_precision,
                "numbers": {k: v["value"] for k, v in harness.check_numbers(
                    cell, tr["readings"], alt, 0, every=True).items()},
                "worst_leaves": {k: reference.worst_leaves(
                    tr["readings"], alt, k, names)
                    for k in ("change1", "change3")}}
        out["program"].append(row)
        print("program", json.dumps(row), flush=True)
        if args.trace_sample:
            xp = sorted(glob.glob(os.path.join(
                tr["trace_dir"], "plugins", "profile", "*", "*.xplane.pb")))
            describe_trace(xp[-1])
            tdata = harness.read_trace(tr["trace_dir"])
            one_window = tr["seconds"] / max(tr["windows"], 1) * 1e9
            trace_reduce.dump(tdata, args.trace_sample, one_window)
            for c in tdata.chips():
                print(f"chip {c}: busy {trace_reduce.busy_ns(tdata, c) / 1e9}"
                      f" s of {tr['seconds']} s; gaps "
                      f"{trace_reduce.idle_gaps(tdata, c, 5)}")
            print("top ops", trace_reduce.top_ops(tdata, 15))
        dump()

    for kind, seeds in (("control", args.control_seeds),
                        ("faults", args.fault_seeds)):
        for seed in _seeds(seeds):
            batches = window_batches(cell, seed)
            want = harness.reference(cell, seed, batches)
            runs = {"control": {"control": True}} if kind == "control" else \
                {"half_batch": {"half_batch": True}}
            if kind == "faults" and cell.K > 1:
                runs["no_average"] = {"average": False}
            for label, kw in runs.items():
                got = harness.reference(cell, seed, batches, **kw)
                nums = harness.check_numbers(cell, got, want, 0, every=True)
                row = {"seed": seed, "kind": label,
                       "numbers": {k: v["value"] for k, v in nums.items()
                                   if k != "window_compiles"}}
                out[kind].append(row)
                print(kind, json.dumps(row), flush=True)
                dump()
    return 0


if __name__ == "__main__":
    sys.exit(main())
