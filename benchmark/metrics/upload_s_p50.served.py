from benchmark import spans


def read(ctx):
    return spans.request_median(ctx, ("transfer.upload",), 1e-9)
