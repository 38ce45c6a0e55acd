"""EF update (``kernels/ef_update.py`` via ``kernels/dispatch.py``): device
time per step of the ops in the exchange's ``ef_update`` stage, the fused
kernel and the relayouts of its wrapper together (``ef_update_roofline``
times the kernel alone).  None where the program opens no stage
scopes."""
from bench import stages, trace


def read(ctx):
    return trace.per_step_max(ctx.reduced, stages.in_stage("ef_update"))
