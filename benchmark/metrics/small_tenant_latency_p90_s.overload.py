import collections

from benchmark.loadgen import percentile


def read(ctx):
    answered = [r for r in ctx.get("requests", []) if "latency_s" in r]
    sent = collections.Counter(r["tenant"] for r in ctx.get("requests", []))
    if len(sent) < 2:
        return None
    largest = sent.most_common(1)[0][0]
    return percentile([r["latency_s"] for r in answered
                       if r["tenant"] != largest], 90)
