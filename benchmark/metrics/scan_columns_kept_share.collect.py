from benchmark import spans


def read(ctx):
    window = spans.trees(ctx)
    if not window:
        return None
    plans = [r.args for tree in window for r in tree
             if r.name == "plan" and r.args and "scan_columns" in r.args]
    have = sum(a["scan_columns"] for a in plans)
    if not have:
        return None
    return 100.0 * sum(a["scan_columns_kept"] for a in plans) / have
