from benchmark import exchange_spans


def read(ctx):
    got = exchange_spans.window(ctx)
    if not got or not ctx.get("peaks"):
        return None
    moved = exchange_spans.maps(got[1])
    split_s = 1e-9 * sum(r.dur_ns
                         for r in exchange_spans.splits(got[1], moved))
    if not split_s:
        return None
    least_s = 2.0 * sum(r.args["bytes"] for r in moved) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    share = 100.0 * least_s / split_s
    if share > 100.0:
        raise RuntimeError(f"exchange split HBM share {share:.1f} % > 100 %: "
                           "the exchange.map bytes or the exchange.split "
                           "span is wrong")
    return share
