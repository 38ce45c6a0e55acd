"""Wire: bytes one node sends per step, the operands of every
collective-permute in the compiled step's HLO text
(``counts.wire_bytes``).  None where the step sends nothing."""
from bench import counts


def read(ctx):
    return counts.wire_bytes(ctx.hlo) or None
