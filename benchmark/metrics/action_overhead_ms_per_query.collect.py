from benchmark import host_spans


def read(ctx):
    return host_spans.per_query(ctx, host_spans.ACTION_BLOCKS, 1e-6)
