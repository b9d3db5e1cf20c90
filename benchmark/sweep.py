"""Find the knee of a served cell once, on the chip: one set-up, several
offered rates, one open-loop window each.

    python -m benchmark.sweep --workload tpch_sf1_server.short_openloop
        [--seconds 30] [--rates 0.5 1 1.5 2] [--seed n]

Without ``--rates`` it measures the mean service time of one request at a
time and offers 0.5, 0.7, 0.9, 1.1 and 1.4 requests per service time. A
backlog grows where more requests are still open at the window's close than
arrive in one second, or one failed. (A rule on the later half's waits
against the earlier half's flags a rate the server keeps up with, where a
third of the requests are joins of 0.3 s.) Prints one line per rate
and the highest rate whose backlog did not grow; the cell's file then gets
four fifths of it, by hand (a cell below the knee, judged on its tails), or
1.2 times it (a cell above the knee, judged on the queries it completes). Each
window runs through the cell's own loop: ``open_overload`` holds a fixed set
of threads at any rate. Prints no result line."""
import argparse
import json
import statistics
import sys
import time

FACTORS = (0.5, 0.7, 0.9, 1.1, 1.4)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2147483777)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", type=float, nargs="+")
    args = ap.parse_args(argv)
    from benchmark import harness, loadgen
    st = harness.setup(args.workload, args.seed, trace=False)
    try:
        t0 = time.perf_counter()
        order = loadgen.cycle(st.workload) * 5
        for qid in order:
            harness.run_query(st, qid)
        service_s = (time.perf_counter() - t0) / len(order)
        print(f"sweep: one request at a time takes {service_s:.4f} s "
              f"(mean of {len(order)})", flush=True)
        rates = args.rates or [f / service_s for f in FACTORS]
        sustained = None
        for rate in rates:
            win = harness.window(st, args.seconds, args.seed, rate=rate)
            done = [r for r in win.requests if "table" in r]
            half = len(done) // 2
            early = statistics.median(r["done"] - r["due"] for r in done[:half])
            late = statistics.median(r["done"] - r["due"] for r in done[half:])
            by_close = sum(r["done"] <= args.seconds for r in done)
            grows = win.attempted - by_close > rate or win.failed > 0
            if not grows:
                sustained = max(sustained or 0.0, rate)
            print("sweep: " + json.dumps({
                "rate_per_s": rate, "attempted": win.attempted,
                "failed": win.failed, "answered_by_close": by_close,
                **win.end_to_end,
                "latency_early_half_p50_s": early,
                "latency_late_half_p50_s": late,
                "drain_s": win.last_done - args.seconds,
                "gen_late_p90_ms": 1e3 * loadgen.percentile(
                    [r["late_s"] for r in done], 90),
                "backlog_grows": grows}), flush=True)
    finally:
        harness.teardown(st)
    print(f"sweep: highest rate sustained {sustained} per s; four fifths of "
          f"it {0.8 * sustained if sustained else None}, 1.2 times it "
          f"{1.2 * sustained if sustained else None}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
