"""Gossip exchange (compression, EF update, permutes, packing): device time
per step of the operations under the trainer's ``obs:exchange`` scope
(``comm/gossip.py``, ``comm/packing.py``)."""
from bench import trace


def read(ctx):
    return trace.per_step_max(ctx.reduced, trace.in_scope("obs:exchange"))
