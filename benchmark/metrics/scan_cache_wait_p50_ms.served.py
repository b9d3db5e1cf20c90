from benchmark import spans


def read(ctx):
    return spans.request_median(ctx, ("scan_cache.wait",), 1e-6)
