from benchmark import spans


def read(ctx):
    return spans.per_query(ctx, ("upload.stage",), 1e-9)
