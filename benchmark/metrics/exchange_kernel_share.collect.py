from benchmark import exchange_spans


def read(ctx):
    got = exchange_spans.window(ctx)
    if not got:
        return None
    split = exchange_spans.splits(got[1])
    if not split:
        return None
    return 100.0 * sum(r.args["path"] == "kernel" for r in split) / len(split)
