from benchmark import spans


def read(ctx):
    return spans.per_query(ctx, ("plan",), 1e-6)
