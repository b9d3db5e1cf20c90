from benchmark import spans


def read(ctx):
    # a scan that ran took time: 0 means the window held no such span
    return spans.per_query(ctx, ("TpuParquetScanExec",), 1e-9) or None
