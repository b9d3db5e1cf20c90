from benchmark import exchange_spans


def read(ctx):
    got = exchange_spans.window(ctx)
    if not got:
        return None
    queries, records = got
    moved = exchange_spans.maps(records)
    if not moved:
        return None
    return 1e-6 * sum(r.args["bytes"] for r in moved) / queries
