from benchmark import host_spans


def read(ctx):
    return host_spans.per_query(ctx, ("scan.wait",), 1e-9)
