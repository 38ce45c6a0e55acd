"""Packing (``comm/packing.py``): device time per step of the ops in the
exchange's ``pack`` and ``unpack`` stages, the state trees copied into
bucket buffers and back to leaves.  None where the program opens no stage
scopes."""
from bench import stages, trace


def read(ctx):
    return trace.per_step_max(ctx.reduced, stages.in_stage("pack", "unpack"))
