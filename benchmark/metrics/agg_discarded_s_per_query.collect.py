from benchmark import spans


def read(ctx):
    window = spans.trees(ctx)
    if not window:
        return None
    attempts = [r for tree in window for r in tree if r.name == "agg.attempt"]
    if not attempts:
        return None
    # 0 where every attempt was kept: that is a reading, not a silence
    return 1e-9 * sum(r.dur_ns for r in attempts
                      if (r.args or {}).get("flagged")) / len(window)
