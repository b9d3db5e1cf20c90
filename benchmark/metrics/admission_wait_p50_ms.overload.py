from benchmark import spans


def read(ctx):
    return spans.request_median(ctx, ("serving.admission_wait",), 1e-6)
