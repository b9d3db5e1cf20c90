"""The two readings a limit of ``correct`` is set from, in one process:

    python -m benchmark.readings --workloads <cell> ... --seeds <n> ...
        [--seconds 8] [--control-seeds 3]

For each seed the tables are made once; each cell is then set up as a run
sets it up, driven through a short window at its own load, and judged as a
run is judged: that is the program's reading (the lower one, over a dozen
seeds). On the first ``--control-seeds`` seeds the control is read too: the
reference computed in float32, the precision below the configuration's
float64, put in the program's place. Prints one ``reading:`` line per seed
and cell, and no result line."""
import argparse
import json
import sys
import time


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workloads", nargs="+", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--scale", type=float,
                    help="default: the cells' configurations' scale_factor")
    ap.add_argument("--any-backend", action="store_true",
                    help="rehearsal: do not insist on a TPU")
    args = ap.parse_args(argv)
    from benchmark import correct, harness, manifest
    from benchmark.datagen import gen_tables
    mf = manifest.load()
    wanted, scales = set(), set()
    for c in args.workloads:
        config = manifest.config_file(
            mf, manifest.workload_entry(mf, c)["config"])
        scales.add(args.scale or config["scale_factor"])
        wanted |= set(manifest.tables_named(
            [q["id"] for q in manifest.workload_file(c)["queries"]],
            config["schema"]))
    if len(scales) > 1:
        ap.error(f"the cells' scale factors differ ({sorted(scales)}): one "
                 "set of tables cannot serve them")
    (scale,) = scales
    bad = 0
    for i, seed in enumerate(args.seeds):
        tables, qids = gen_tables(sorted(wanted), scale, seed), set()
        for cell in args.workloads:
            t0 = time.perf_counter()
            st = harness.setup(cell, seed, False, scale,
                               not args.any_backend, tables=tables)
            try:
                win = harness.window(st, args.seconds, seed)
            finally:
                harness.teardown(st)
            ok, numbers = correct.judge(win.answers, st.tables,
                                        len(st.cpu_execs), win.unanswered,
                                        win.failed - win.unanswered)
            bad += not ok
            qids |= set(st.qids)
            print("reading: " + json.dumps({
                "seed": seed, "cell": cell, "correct": ok,
                "attempted": win.attempted, "failed": win.failed,
                "rows": {t: tables[t].num_rows for t in st.tables},
                "numbers": {n["name"]: n["value"] for n in numbers},
                "took_s": round(time.perf_counter() - t0, 1)}), flush=True)
        if i < args.control_seeds:
            gaps = correct.control_gaps(tables, sorted(qids))
            print("control: " + json.dumps({
                "seed": seed, "float32": {
                    q: {"exact_mismatch": m, "rel_gap": g}
                    for q, (m, g) in gaps.items()}}), flush=True)
    print(f"readings: {bad} not correct", file=sys.stderr)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
