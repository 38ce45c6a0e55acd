"""Compression: the least time of one node's selection, reading every
parameter's delta once and writing the kept values and int32 indices
(``counts.topk_bytes``) at peak HBM bandwidth, over ``compress_ms``, the
device time of the exchange's ``compress`` stage, in %.  Timed by the
stage and not by an op name, it reads whatever kernel does the
selection."""
from bench import counts, peaks
from bench.metrics import compress_ms


def read(ctx):
    ms = compress_ms.read(ctx)
    if ms is None:
        return None
    least = counts.topk_bytes(ctx.cell.model, ctx.cell.traffic["fraction"]) \
        / peaks.peaks(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / (ms / 1e3)
