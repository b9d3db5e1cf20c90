from benchmark import spans


def read(ctx):
    window = spans.trees(ctx)
    if not window:
        return None
    # a query without an aggregate has no attempt, and neither has a program
    # from before the span: no reading either way
    attempts = sum(r.name == "agg.attempt" for tree in window for r in tree)
    return attempts / len(window) if attempts else None
