from benchmark import spans


def read(ctx):
    window = spans.trees(ctx)
    if not window:
        return None
    forms = [(r.args or {}).get("reduce") for tree in window for r in tree
             if r.name == "agg.attempt"]
    # a program from before the arg notes none: no reading, as without spans
    if not forms or None in forms:
        return None
    # 0 where no attempt's groups were dense: that is a reading
    return sum(f == "scan" for f in forms) / len(window)
