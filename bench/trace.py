"""Reduce a profiler trace of the window to device intervals and scopes.

The trace (``.xplane.pb``, read with ``jax.profiler.ProfileData``) holds,
per TPU device plane ``/device:TPU:<n>``, a line ``XLA Modules`` (one event
per execution of the train step) and a line ``XLA Ops`` (one event per HLO
instruction executed, named ``%<instruction> = <type> <opcode>(...)``).
The events carry no scope, so each instruction is mapped back through the
compiled text of the traced program to its ``op_name`` metadata, where
the trainer's phase scopes (``obs:grad``, ``obs:optimizer``,
``obs:exchange``) appear.  Loops and calls (``while``, ``conditional``,
``call``) are containers whose bodies appear as events of their own; they
are dropped so that no time counts twice.  The host plane ``/host:CPU``
holds the harness's own spans (``bench:input``, ``bench:dispatch``,
``bench:wait``), on the same clock.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import importlib
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

CONTAINERS = ("while", "conditional", "call")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = .*? ([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+) .*\{\s*$")
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_EVENT = re.compile(r"^%([\w.\-]+) = .*? ([a-z][\w\-]*)\(")


@dataclasses.dataclass
class Op:
    start: int          # ns, on the trace's clock
    dur: int            # ns
    name: str           # HLO instruction
    opcode: str
    op_name: str        # scope path from the instruction's metadata

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclasses.dataclass
class Reduced:
    #: per device plane: leaf operations and train-step executions
    ops: Dict[str, List[Op]]
    steps: Dict[str, List[Tuple[int, int]]]
    #: harness spans on the host: (name, start, end)
    host: List[Tuple[str, int, int]]
    #: the traced window on the host clock: first span start, last end
    window: Tuple[int, int]

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over devices."""
        lo, hi = self.window
        return sum(_covered(_clip([(o.start, o.end) for o in ops], lo, hi))
                   for ops in self.ops.values()) / len(self.ops) / 1e9


@dataclasses.dataclass
class Context:
    """What a per-layer reader may read."""
    cell: object
    reduced: Reduced
    tokens_per_s: float
    device_kind: str
    #: the compiled step's HLO text
    hlo: str = ""


def xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {found}")
    return found[0]


def hlo_scopes(hlo_text: str) -> Dict[str, Tuple[str, str]]:
    """instruction name -> (opcode, op_name) for every instruction.  A
    fusion that carries no metadata of its own takes that of its fused
    computation's root, or else of the first of its instructions that has
    one."""
    out, comp_scope, calls = {}, {}, {}
    comp, first, root = None, "", ""
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            comp, first, root = head.group(1), "", ""
            continue
        if line.startswith("}") and comp is not None:
            comp_scope[comp] = root or first
            comp = None
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        meta = _OP_NAME.search(line)
        op_name = meta.group(1) if meta else ""
        first = first or op_name
        if line.lstrip().startswith("ROOT"):
            root = op_name
        out[m.group(1)] = (m.group(2), op_name)
        called = _CALLS.search(line)
        if called and not op_name:
            calls[m.group(1)] = called.group(1)
    for name, comp in calls.items():
        out[name] = (out[name][0], comp_scope.get(comp, ""))
    return out


def reduce_file(path: str, hlo_text: str) -> Reduced:
    """Reduce the ``.xplane.pb`` at ``path`` (plain or gzipped)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    scopes = hlo_scopes(hlo_text)
    ops, steps, host = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: list(ln.events) for ln in plane.lines}
            leaf = []
            for e in lines.get("XLA Ops", []):
                m = _EVENT.match(e.name)
                if not m:
                    continue
                opcode, op_name = scopes.get(m.group(1), (m.group(2), ""))
                if opcode in CONTAINERS:
                    continue
                leaf.append(Op(int(e.start_ns), int(e.duration_ns),
                               m.group(1), opcode, op_name))
            ops[plane.name] = leaf
            steps[plane.name] = [(int(e.start_ns),
                                  int(e.start_ns + e.duration_ns))
                                 for e in lines.get("XLA Modules", [])
                                 if "train_step" in e.name]
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                host += [(e.name, int(e.start_ns),
                          int(e.start_ns + e.duration_ns))
                         for e in ln.events if e.name.startswith("bench:")]
    if not ops or not host:
        raise ValueError(f"{path}: no TPU device plane or no harness spans")
    host.sort(key=lambda s: s[1])
    return Reduced(ops=ops, steps=steps, host=host,
                   window=(host[0][1], max(s[2] for s in host)))


def reduce(trace_dir: str, hlo_text: str) -> Reduced:
    return reduce_file(xplane(trace_dir), hlo_text)


# -- interval arithmetic -------------------------------------------------------

def _union(iv: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def _covered(iv) -> int:
    return sum(b - a for a, b in _union(iv))


def _minus(iv, cut) -> List[Tuple[int, int]]:
    """Parts of ``iv`` not covered by ``cut``."""
    out, cut, j = [], _union(cut), 0
    for a, b in _union(iv):
        while j < len(cut) and cut[j][1] <= a:
            j += 1
        k = j
        while k < len(cut) and cut[k][0] < b and a < b:
            c, d = cut[k]
            if c > a:
                out.append((a, c))
            a = max(a, d)
            k += 1
        if a < b:
            out.append((a, b))
    return out


def idle(red: Reduced, device: str) -> List[Tuple[int, int]]:
    """Intervals of the window in which no operation ran on ``device``."""
    lo, hi = red.window
    return _minus([(lo, hi)], _clip([(o.start, o.end)
                                     for o in red.ops[device]], lo, hi))


def host_activity(red: Reduced, a: int, b: int) -> str:
    """The harness span that overlaps [a, b) the most (``none`` if none)."""
    best, name = 0, "none"
    for span, s, e in red.host:
        ov = min(b, e) - max(a, s)
        if ov > best:
            best, name = ov, span
    return name


def per_step_max(red: Reduced, select) -> Optional[float]:
    """Largest, over devices, of the device time per step of the ops that
    ``select(op)`` picks, in ms; None when no device has any."""
    worst = None
    for dev, ops in red.ops.items():
        n = len(red.steps[dev])
        t = sum(o.dur for o in ops if select(o))
        if n and t:
            worst = max(worst or 0.0, t / n / 1e6)
    return worst


def in_scope(scope: str):
    return lambda o: f"/{scope}/" in o.op_name + "/"


def read_metric(name: str, ctx: Context) -> Optional[float]:
    """Run the reader ``bench/metrics/<name>.py``; None when it finds
    nothing to read."""
    return importlib.import_module(f"bench.metrics.{name}").read(ctx)


def breakdown(red: Reduced) -> Dict[str, list]:
    """The ten costliest operations by scope (seconds per device, summed
    over the window) and the ten largest idle shares by host activity."""
    by_op = defaultdict(int)
    gaps = defaultdict(int)
    for dev, ops in red.ops.items():
        for o in ops:
            label = o.op_name.replace("jit(train_step)/", "") or o.name
            by_op[label] += o.dur
        inside = _union(red.steps[dev])
        for a, b in idle(red, dev):
            stepped = _covered(_clip(inside, a, b))
            if stepped:
                gaps["in-step"] += stepped
            if b - a - stepped > 0:
                gaps[host_activity(red, a, b)] += b - a - stepped
    n = len(red.ops)
    top = lambda d: [[k, v / n / 1e9] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {"device_ops": top(by_op), "idle_gaps": top(gaps)}
