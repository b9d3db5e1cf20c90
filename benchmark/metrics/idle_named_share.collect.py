from benchmark import host_spans


def read(ctx):
    return host_spans.idle_named_share(ctx)
