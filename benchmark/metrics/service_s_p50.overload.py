from benchmark.loadgen import percentile


def read(ctx):
    values = [r["done"] - r["due"] - r["queue_wait_s"]
              for r in ctx.get("requests", [])
              if "table" in r and r.get("queue_wait_s") is not None]
    return percentile(values, 50)
