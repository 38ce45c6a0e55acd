"""The trace reduction and the per-layer readers.

On a trace recorded on the chip (``bench/testdata``: a traced run of
qwen3-1.7b.topk.1chip, its compiled HLO text and the run's result line),
the readers give again exactly the per-layer numbers the run printed.
Small hand-made cases pin the interval arithmetic, the scope mapping and
how the readers treat a collective permute, which the one-chip trace has
none of.
"""
import gzip
import json
import os

import pytest

from bench import cell as cells
from bench import trace

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")


def test_interval_arithmetic():
    assert trace._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace._minus([(0, 10)], [(2, 3), (5, 7), (9, 12)]) == \
        [(0, 2), (3, 5), (7, 9)]
    assert trace._minus([(0, 4), (6, 10)], [(3, 7)]) == [(0, 3), (7, 10)]
    assert trace._covered([(0, 4), (2, 6), (8, 9)]) == 7
    assert trace._clip([(0, 4), (5, 9)], 2, 6) == [(2, 4), (5, 6)]


HLO = """\
HloModule jit_train_step

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %m = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(train_step)/obs:optimizer/mul"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1
  %sort.2 = (f32[8]{0}, s32[8]{0}) sort(%a, %a), dimensions={0}, metadata={op_name="jit(train_step)/obs:exchange/jit(block_topk_select)/top_k"}
  ROOT %copy.3 = f32[8]{0} copy(%fusion.1)
}
"""


def test_scopes_from_hlo_metadata():
    s = trace.hlo_scopes(HLO)
    assert s["fusion.1"] == ("fusion", "jit(train_step)/obs:optimizer/mul")
    assert s["sort.2"][1].endswith("/top_k")
    assert s["copy.3"] == ("copy", "")


def _op(start, dur, opcode, op_name=""):
    return trace.Op(start, dur, f"{opcode}.{start}", opcode, op_name)


def test_collective_and_host_gap_readers():
    ops = [_op(0, 40, "fusion", "jit(train_step)/obs:grad/dot"),
           _op(30, 30, "collective-permute-done",
               "jit(train_step)/obs:exchange/ppermute"),
           _op(70, 20, "fusion", "jit(train_step)/obs:exchange/add")]
    red = trace.Reduced(ops={"/device:TPU:0": ops},
                        steps={"/device:TPU:0": [(0, 90)]},
                        host=[("bench:dispatch", -10, 0),
                              ("bench:wait", 0, 100)],
                        window=(-10, 100))
    ctx = trace.Context(cell=None, reduced=red, tokens_per_s=1.0,
                        device_kind="TPU v5 lite")
    # the permute counts with the exchange
    assert trace.read_metric("exchange_ms", ctx) == 50 / 1e6
    # idle outside the step: -10..0 and 90..100
    assert trace.read_metric("host_gap_ms", ctx) == 20 / 1e6
    assert trace.read_metric("grad_ms", ctx) == 40 / 1e6
    assert red.busy_s() == 80e-9
    gaps = dict(trace.breakdown(red)["idle_gaps"])
    assert gaps == {"in-step": 10e-9, "bench:dispatch": 10e-9,
                    "bench:wait": 10e-9}


def _recorded():
    with open(os.path.join(DATA, "qwen3-1.7b.topk.1chip.result.json")) as f:
        return json.load(f)


def test_readers_reproduce_the_recorded_run():
    result = _recorded()
    with gzip.open(os.path.join(DATA, "qwen3-1.7b.topk.1chip.hlo.txt.gz"),
                   "rt") as f:
        hlo = f.read()
    red = trace.reduce_file(
        os.path.join(DATA, "qwen3-1.7b.topk.1chip.xplane.pb.gz"), hlo)
    cell = cells.load("qwen3-1.7b.topk.1chip")
    window_s = result["device"]["window_s"]
    tokens = result["attempted"] * 4096
    ctx = trace.Context(cell=cell, reduced=red,
                        tokens_per_s=tokens / window_s,
                        device_kind=result["device"]["kind"])
    got = {m["name"]: trace.read_metric(m["name"], ctx)
           for m in cell.per_layer}
    want = {k: v["value"] for k, v in result["metrics"].items()}
    assert {k: v for k, v in got.items() if v is not None} == \
        pytest.approx(want, rel=1e-12)
    assert red.busy_s() == pytest.approx(result["device"]["busy_s"],
                                         rel=1e-12)
    assert trace.breakdown(red) == result["breakdown"]
