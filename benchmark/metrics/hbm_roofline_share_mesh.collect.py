def read(ctx):
    """Least time the traced chips could take together for the slice's
    queries, as a share of the time each was busy on average."""
    t = ctx.get("trace")
    if not t or not t["queries"] or not t["busy_s"] or not t.get("chips"):
        return None
    peak = ctx["peaks"]["hbm_bytes_per_s"] * t["chips"]
    share = 100.0 * t["least_bytes"] / peak / t["busy_s"]
    if share > 100.0:
        raise RuntimeError(f"hbm roofline share {share:.1f} % > 100 % on "
                           f"{t['chips']} chips: the least-bytes count or "
                           "the busy time is wrong")
    return share
