from benchmark import host_spans


def read(ctx):
    return host_spans.per_query(ctx, ("exchange.fetch",), 1e-6)
