"""Local optimizer half-step: device time per step of the operations under
the trainer's ``obs:optimizer`` scope (``optim/sgd.py``)."""
from bench import trace


def read(ctx):
    return trace.per_step_max(ctx.reduced, trace.in_scope("obs:optimizer"))
