from benchmark import spans


def read(ctx):
    return spans.per_query(ctx, ("query", "action"), 1e-6, "self_ns")
