"""Forward and backward pass: device time per step of the operations under
the trainer's ``obs:grad`` scope (``models/transformer.py``)."""
from bench import trace


def read(ctx):
    return trace.per_step_max(ctx.reduced, trace.in_scope("obs:grad"))
