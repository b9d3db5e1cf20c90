from benchmark import exchange_spans


def read(ctx):
    got = exchange_spans.window(ctx)
    if not got:
        return None
    queries, records = got
    noted = [r.args["sketches"] for r in exchange_spans.maps(records)
             if "sketches" in r.args]
    # a program from before the arg notes none: no reading, as without spans
    if not noted:
        return None
    # 0 where no repartitioning exchange hashed its keys: that is a reading
    return sum(noted) / queries
