import json
import os

from benchmark import spans

with open(os.path.splitext(os.path.abspath(__file__))[0] + ".json") as f:
    ICI_BYTES_PER_S = json.load(f)["ici_bytes_per_s"]


def read(ctx):
    window = spans.trees(ctx)
    if not window:
        return None
    records = [r for tree in window for r in tree]
    least_s = sum(r.args["max_shard_bytes"] / ICI_BYTES_PER_S
                  for r in records if r.name == "mesh.exchange" and r.args)
    moved_s = 1e-9 * sum(r.dur_ns for r in records
                         if r.name == "mesh.exchange.move")
    if not moved_s:
        return None
    share = 100.0 * least_s / moved_s
    if share > 100.0:
        raise RuntimeError(f"mesh exchange ICI share {share:.1f} % > 100 %: "
                           "max_shard_bytes or the move span is wrong")
    return share
