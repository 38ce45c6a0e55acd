"""Gossip mix (``comm/gossip.py``): device time per step of the ops in the
exchange's ``mix`` stage, the collective-permutes that carry the
neighbours' messages, the received payloads made dense, and the mixing
weights.  None where the program opens no stage scopes."""
from bench import stages, trace


def read(ctx):
    return trace.per_step_max(ctx.reduced, stages.in_stage("mix"))
