from benchmark import host_spans


def read(ctx):
    return host_spans.per_query(ctx, ("scan.chunk_decode",), 1e-9)
