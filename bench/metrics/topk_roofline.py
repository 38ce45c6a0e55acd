"""Compression (``core/compression.py`` TopK via ``comm/packing.py``): the
least time of one node's selection, reading every parameter's delta once
and writing the kept values and int32 indices (``counts.topk_bytes``) at
peak HBM bandwidth, over the device time per step of the top-k operations
under ``obs:exchange``, in %.  It selects ``lax.top_k`` by name, so it
reads nothing once a selection of another name replaces it; ``exchange_ms``
carries the comparison then."""
from bench import counts, peaks, trace


def _topk(op):
    return trace.in_scope("obs:exchange")(op) and "/top_k" in op.op_name


def read(ctx):
    ms = trace.per_step_max(ctx.reduced, _topk)
    if ms is None:
        return None
    least = counts.topk_bytes(ctx.cell.model, ctx.cell.traffic["fraction"]) \
        / peaks.peaks(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / (ms / 1e3)
