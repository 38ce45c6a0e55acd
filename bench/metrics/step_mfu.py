"""Whole step: model operations (forward and backward, recomputation
excluded, ``counts.flops_per_token``) times the tokens per second of the
traced window, over the chips' bf16 peak (``peaks.py``), in %."""
from bench import counts, peaks


def read(ctx):
    model, tr = ctx.cell.model, ctx.cell.traffic
    ops_per_s = counts.flops_per_token(model, tr["seq_len"]) * ctx.tokens_per_s
    peak = peaks.peaks(ctx.device_kind)["bf16_flops"] * ctx.cell.chips
    return 100.0 * ops_per_s / peak
