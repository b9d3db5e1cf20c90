from benchmark import spans


def read(ctx):
    window = spans.trees(ctx)
    if not window:
        return None
    shipped = [r.args["wire_bytes"] for tree in window for r in tree
               if r.name == "mesh.exchange" and r.args]
    if not shipped:
        return None
    return 1e-6 * sum(shipped) / len(window)
