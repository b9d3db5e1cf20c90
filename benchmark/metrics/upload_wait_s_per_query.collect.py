from benchmark import spans


def read(ctx):
    return spans.per_query(ctx, ("upload.wait",), 1e-9)
