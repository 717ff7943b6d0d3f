"""The chip benchmark's command: one run of one cell.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Prints the numbers the check compared, each beside its limit, as the last
lines of standard error, and one JSON object as the last line of standard
output: ``correct``, ``attempted`` (timed CoDA windows), ``failed``
(windows with a non-finite loss), ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``.  Exits non-zero with no
result when JAX finds no TPU or fewer chips than the cell asks for.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                   "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness

    cell = harness.Cell(args.workload)
    harness.configure_cache()
    problem = harness.device_check(cell.chips)
    if problem:
        print(f"run.py: {problem}", file=sys.stderr)
        return 3
    result, lines = harness.run(cell, args.seed, args.seconds,
                                bool(args.trace), t_process=T_PROCESS)
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
