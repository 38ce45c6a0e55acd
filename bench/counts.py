"""Operations and bytes from shapes: the yardstick the readers divide by.

Everything here is computed from a configuration's sizes (the ``model``
section of ``bench/configs/<config>.json``) and a traffic mix, never read
from the program, so a later change to the program cannot move it.

* Parameter leaves and their sizes, in the order ``jax.tree`` flattens the
  parameter dict that the model's family (``bench/families``) describes
  (sorted keys).
* The compressor's bucket layout (:func:`bucket_plan`): the packing rule of
  the CHOCO exchange as the configuration runs it.  Leaves in flatten
  order, each padded to 128 elements, share a bucket until it would pass
  4 Mi elements; a leaf larger than that has a bucket of its own, cut into
  rows of 4 Mi elements, and its top-k budget is spread evenly over the
  rows.  The reference compresses by this plan.
* Model operations per token (forward + backward, recomputation excluded).
* Least bytes of the top-k selection and of the fused EF update.
* Bytes one node sends per step, parsed from a compiled step's HLO text.
"""
from __future__ import annotations

import math
import re
from typing import Dict, List, Tuple

from bench import families

LANES = 128
#: largest bucket (and top-k row) of the packed exchange, in elements
MAX_BUCKET = 1 << 22
F32 = 4


def param_shapes(model: Dict) -> Dict:
    """One node's parameter shapes, as the nested dict the model's family
    holds them in (a node's own copy; the trainer stacks nodes first)."""
    return families.load(model["family"]).param_shapes(model)


def leaf_shapes(model: Dict) -> List[Tuple[str, Tuple[int, ...]]]:
    """(path, shape) of one node's parameter leaves, in the order
    ``jax.tree`` flattens them (dict keys sorted)."""
    out: List[Tuple[str, Tuple[int, ...]]] = []

    def walk(prefix, node):
        for key in sorted(node):
            if isinstance(node[key], dict):
                walk(prefix + key + "/", node[key])
            else:
                out.append((prefix + key, node[key]))
    walk("", param_shapes(model))
    return out


def n_params(model: Dict) -> int:
    return sum(math.prod(s) for _, s in leaf_shapes(model))


def topk_budget(size: int, fraction: float) -> int:
    """Coordinates kept of a leaf of ``size`` elements at ``fraction``."""
    return max(1, min(size, math.ceil(fraction * size)))


def bucket_plan(sizes: List[int], fraction: float) -> List[Dict]:
    """The exchange's buckets for leaves of ``sizes`` (flatten order).

    Each bucket: ``slots`` [(leaf, offset, size)], padded ``size``,
    ``logical`` (elements that are not padding), ``budget`` (the summed
    top-k budget of its leaves, which sets its omega = budget / logical),
    ``rows`` (1, or the 4 Mi-element rows of an oversized leaf) and ``k``
    (kept per row)."""
    buckets: List[Dict] = []
    open_b = None
    for i, size in enumerate(sizes):
        seg = -(-size // LANES) * LANES
        if open_b is None or (open_b["size"] + seg > MAX_BUCKET
                              and open_b["size"] > 0):
            open_b = {"slots": [], "size": 0, "logical": 0}
            buckets.append(open_b)
        open_b["slots"].append((i, open_b["size"], size))
        open_b["size"] += seg
        open_b["logical"] += size
    for b in buckets:
        k = min(sum(topk_budget(s, fraction) for _, _, s in b["slots"]),
                b["logical"])
        b["budget"] = k
        if b["size"] > MAX_BUCKET:
            b["rows"] = -(-b["size"] // MAX_BUCKET)
            b["k"] = max(1, -(-k // b["rows"]))
        else:
            b["rows"], b["k"] = 1, k
    return buckets


def kept_per_node(model: Dict, fraction: float) -> int:
    """Coordinates one node's compressed message holds."""
    plan = bucket_plan([math.prod(s) for _, s in leaf_shapes(model)],
                       fraction)
    return sum(b["rows"] * b["k"] for b in plan)


def topk_bytes(model: Dict, fraction: float) -> int:
    """Least bytes of one node's top-k selection: read every parameter's
    delta once, write the kept values and their int32 indices."""
    return F32 * n_params(model) + 8 * kept_per_node(model, fraction)


def ef_update_bytes(model: Dict) -> int:
    """Least bytes of one node's fused EF update: five streams read
    (x_half, x_hat, s, q_self, q_nbr) and three written (x, x_hat, s)."""
    return 8 * F32 * n_params(model)


def flops_per_token(model: Dict, seq_len: int) -> float:
    """Forward + backward operations per token, recomputation excluded,
    by the model's family."""
    return families.load(model["family"]).flops_per_token(model, seq_len)


_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "f8e4m3fn": 1, "f8e5m2": 1}
_ARRAY_RE = re.compile(r"\b([a-z]+[0-9]*[a-z0-9]*)\[([0-9,]*)\]")
_PERMUTE = re.compile(r"\scollective-permute(?:-start)?\(")
_DEFINES = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (.*?) [a-z][\w\-]*\(")
_OPERAND = re.compile(r"%([\w.\-]+)")


def _array_bytes(text: str) -> int:
    total = 0
    for dtype, dims in _ARRAY_RE.findall(text):
        if dtype not in _DTYPE_BYTES:
            raise ValueError(f"unknown HLO element type {dtype!r} in {text!r}")
        total += _DTYPE_BYTES[dtype] * math.prod(
            int(d) for d in dims.split(",") if d)
    return total


def _operands(line: str, start: int) -> str:
    """The text between the parenthesis that opens at ``start`` and the
    one that closes it (layouts such as ``{0:T(1024)}`` nest)."""
    depth = 0
    for i in range(start, len(line)):
        if line[i] == "(":
            depth += 1
        elif line[i] == ")":
            depth -= 1
            if depth == 0:
                return line[start + 1:i]
    raise ValueError(f"unbalanced operand list: {line!r}")


def wire_bytes(hlo_text: str) -> int:
    """Bytes one device sends per execution of a compiled SPMD program:
    the operands of every collective-permute (each goes to one peer), each
    with the type that the instruction defining it gives (the compiled
    text names an operand without its type)."""
    types, sent = {}, []
    for line in hlo_text.splitlines():
        d = _DEFINES.match(line)
        if d:
            types[d.group(1)] = d.group(2)
        m = _PERMUTE.search(line)
        if m:
            sent += _OPERAND.findall(_operands(line, m.end() - 1))
    return sum(_array_bytes(types[name]) for name in sent)
