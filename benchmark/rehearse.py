"""Rehearsal: the same set-up, window and reduction code as a run, at
SF0.01 for a few seconds on whatever backend jax finds.

    python -m benchmark.rehearse --workload <cell> [--trace 1]

Prints ``rehearsal: ok platform=<p>`` and NO result line, so that a number
from a CPU can never be read as a metric."""
import argparse
import sys
import time


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2147483999)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from benchmark import harness, manifest
    manifest.validate(manifest.load())
    result, numbers, _ = harness.run_cell(
        args.workload, args.seed, args.seconds, bool(args.trace),
        time.perf_counter(), scale=0.01, need_tpu=False)
    from benchmark.run import print_compared
    print_compared(numbers, sys.stderr)
    print(f"rehearsal: metrics named {sorted(result['metrics'])} "
          f"attempted={result['attempted']} failed={result['failed']}",
          file=sys.stderr)
    if not result["correct"]:
        print("rehearsal: NOT correct", file=sys.stderr)
        return 1
    print(f"rehearsal: ok platform={result['device']['platform']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
