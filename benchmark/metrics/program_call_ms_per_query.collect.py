from benchmark import spans


def read(ctx):
    return spans.per_query(ctx, ("program.",), 1e-6)
