"""Compression (``core/compression.py`` via ``comm/packing.py``): device
time per step of the ops in the exchange's ``compress`` stage (the delta,
the selection, the payload and the own dense q), whatever kernel does the
selection.  None where the program opens no stage scopes."""
from bench import stages, trace


def read(ctx):
    return trace.per_step_max(ctx.reduced, stages.in_stage("compress"))
