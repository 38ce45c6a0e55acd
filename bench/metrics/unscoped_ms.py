"""Unattributed: device time per step of the ops that carry no phase scope
at all (``stages.unscoped``), such as the loops, fusions and copies XLA
makes without an ``op_name``; the part of the step no other reader sees."""
from bench import stages, trace


def read(ctx):
    return trace.per_step_max(ctx.reduced, stages.unscoped)
