"""EF update kernel (``kernels/ef_update.py`` via ``kernels/dispatch.py``):
the least time of one node's update, five streams read and three written
over every parameter (``counts.ef_update_bytes``) at peak HBM bandwidth,
over the device time per step of the fused kernel (the custom call whose
scope names ``ef_gossip_update``), in %."""
from bench import counts, peaks, trace


def _kernel(op):
    return op.opcode == "custom-call" and "ef_gossip_update" in op.op_name


def read(ctx):
    ms = trace.per_step_max(ctx.reduced, _kernel)
    if ms is None:
        return None
    least = counts.ef_update_bytes(ctx.cell.model) \
        / peaks.peaks(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / (ms / 1e3)
