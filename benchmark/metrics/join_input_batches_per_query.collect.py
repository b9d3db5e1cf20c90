from benchmark import join_spans


def read(ctx):
    got = join_spans.window(ctx)
    if not got:
        return None
    queries, drains = got
    noted = [r.args["batches"] for r in drains
             if r.args and "batches" in r.args]
    # a program from before the arg notes none: no reading
    if not noted:
        return None
    return sum(noted) / queries
