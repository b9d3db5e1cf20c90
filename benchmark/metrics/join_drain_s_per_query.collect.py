from benchmark import join_spans


def read(ctx):
    got = join_spans.window(ctx)
    if not got:
        return None
    queries, drains = got
    return 1e-9 * sum(r.dur_ns for r in join_spans.outermost(ctx, drains)) \
        / queries
