"""The stages of the gossip exchange in a reduced trace.

Below the trainer's ``obs:exchange`` scope the program opens one named
scope per stage of the exchange (``pack``, ``compress``, ``mix``,
``ef_update``, ``unpack``).  An op belongs to the first of these that its
``op_name`` holds as a path component below ``obs:exchange``, wherever it
sits there: inside a ``lax.switch`` or ``lax.cond`` branch the name stack
puts a component such as ``branch_0_fun`` between the two.  A program
that opens no stage scopes, as before they existed, has no op in any
stage, and the readers of stages read nothing.
"""
from __future__ import annotations

from typing import Optional

STAGES = ("pack", "compress", "mix", "ef_update", "unpack")
EXCHANGE = "obs:exchange"


def stage_of(op) -> Optional[str]:
    """The exchange stage ``op`` runs in, or None."""
    parts = op.op_name.split("/")
    if EXCHANGE not in parts:
        return None
    for part in parts[parts.index(EXCHANGE) + 1:]:
        if part in STAGES:
            return part
    return None


def in_stage(*names: str):
    """Selector of the ops that run in one of the stages ``names``."""
    return lambda op: stage_of(op) in names


def unscoped(op) -> bool:
    """Whether ``op`` carries no phase scope at all: no ``obs:`` component
    in its ``op_name``, as for the ops XLA makes without one."""
    return "obs:" not in op.op_name
