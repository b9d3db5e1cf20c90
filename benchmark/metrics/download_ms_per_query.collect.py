from benchmark import spans


def read(ctx):
    return spans.per_query(ctx, ("download.wait", "download.to_arrow"), 1e-6)
