from benchmark import spans


def read(ctx):
    # an exchange that ran took time: 0 means the window held no such span
    return spans.per_query(ctx, ("mesh.exchange",), 1e-9) or None
