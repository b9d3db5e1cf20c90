from benchmark import host_spans


def read(ctx):
    return host_spans.decoded_mb_per_query(ctx)
