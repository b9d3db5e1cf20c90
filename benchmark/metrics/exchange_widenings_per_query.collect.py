from benchmark import exchange_spans


def read(ctx):
    got = exchange_spans.window(ctx)
    if not got:
        return None
    queries, records = got
    split = exchange_spans.splits(records)
    if not split:
        return None
    return sum(r.args.get("widenings", 0) for r in split) / queries
