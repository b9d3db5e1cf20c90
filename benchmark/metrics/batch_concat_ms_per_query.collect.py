from benchmark import host_spans


def read(ctx):
    return host_spans.per_query(ctx, ("batch.concat",), 1e-6)
