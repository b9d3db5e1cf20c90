from benchmark import host_spans


def read(ctx):
    return host_spans.per_query(ctx, ("scan.arrow_read",), 1e-9)
