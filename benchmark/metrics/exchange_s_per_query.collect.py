from benchmark import exchange_spans


def read(ctx):
    got = exchange_spans.window(ctx)
    if not got:
        return None
    queries, records = got
    # an exchange that ran took time: 0 means the window held no such span
    return 1e-9 * sum(r.dur_ns for r in exchange_spans.maps(records)) \
        / queries or None
