"""The benchmark's command.

    python -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python -m benchmark.run --validate

One process per run, no child. Fails, printing no result, without a TPU of a
kind that ``peaks.json`` knows. The last line of standard output is the
result object; the numbers compared for ``correct`` are also the last lines of
standard error, each beside its limit."""
import time

T_START = time.perf_counter()  # before any heavy import: set-up starts here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def print_compared(numbers, out):
    for n in numbers:
        word = ">=" if n["holds"] == "at_least" else "<="
        print(f"compared: {n['name']} = {n['value']!r} (limit {word} "
              f"{n['limit']!r})", file=out)
    out.flush()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--validate", action="store_true",
                    help="check BENCHMARK.json and the files it names, "
                         "run nothing")
    args = ap.parse_args(argv)
    from benchmark import manifest
    mf = manifest.load()
    manifest.validate(mf)
    if args.validate:
        print(f"valid: {len(mf['workloads'])} cells, "
              f"{len(mf['end_to_end'])} end-to-end and "
              f"{len(mf['per_layer'])} per-layer metrics")
        return 0
    if not args.workload:
        ap.error("--workload is required")
    seconds = args.seconds if args.seconds else mf["run_seconds"]
    from benchmark import harness
    result, numbers, _ = harness.run_cell(
        args.workload, args.seed, seconds, bool(args.trace), T_START)
    sys.stdout.flush()
    print_compared(numbers, sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
