"""The readers of the exchange's stages (``bench/stages.py``).

Hand-made op lists pin which stage an op belongs to (the stage may sit
below a ``lax.switch`` branch component, the first stage component wins,
``obs:grad`` has no stages) and what each reader makes of them.  On the
trace recorded on the chip before the program opened stage scopes, the
stage readers read nothing, and the time no scope claims
(``unscoped_ms``) is the recorded figure.
"""
import gzip
import os

import pytest

from bench import cell as cells
from bench import counts, peaks, stages, trace

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")
STEP = "jit(train_step)"


def _op(start, dur, op_name, opcode="fusion"):
    return trace.Op(start, dur, f"{opcode}.{start}", opcode, op_name)


@pytest.mark.parametrize("op_name, stage", [
    (f"{STEP}/obs:exchange/compress/jit(block_topk_select)/top_k",
     "compress"),
    (f"{STEP}/obs:exchange/shard_map/branch_0_fun/compress/sub", "compress"),
    (f"{STEP}/obs:exchange/pack/concatenate", "pack"),
    (f"{STEP}/obs:exchange/unpack/dynamic_slice", "unpack"),
    (f"{STEP}/obs:exchange/mix/mul", "mix"),
    (f"{STEP}/obs:exchange/ef_update/jit(ef_gossip_update_vector)/reshape",
     "ef_update"),
    (f"{STEP}/obs:exchange/mix/jit(compress)/unpack/x", "mix"),
    (f"{STEP}/obs:exchange/reshape", None),
    (f"{STEP}/obs:grad/compress/dot_general", None),
    ("compress/scatter", None),
    ("", None),
])
def test_stage_of(op_name, stage):
    assert stages.stage_of(_op(0, 1, op_name)) == stage


def _ctx(ops, n_steps=2, cell=None):
    red = trace.Reduced(ops={"/device:TPU:0": ops},
                        steps={"/device:TPU:0": [(0, 1)] * n_steps},
                        host=[("bench:dispatch", 0, 1)], window=(0, 1))
    return trace.Context(cell=cell, reduced=red, tokens_per_s=1.0,
                         device_kind="TPU v5 lite")


EX = f"{STEP}/obs:exchange"
SCOPED = [_op(0, 40, f"{EX}/pack/reshape"),
          _op(40, 2000, f"{EX}/compress/top_k", "sort"),
          _op(2040, 600, f"{EX}/shard_map/branch_1_fun/compress/gather"),
          _op(2640, 20, f"{EX}/mix/mul"),
          _op(2660, 300, f"{EX}/ef_update/jit(ef_gossip_update_vector)/x",
              "custom-call"),
          _op(2960, 100, f"{EX}/ef_update/jit(ef_gossip_update_vector)/y"),
          _op(3060, 60, f"{EX}/unpack/slice"),
          _op(3120, 7, f"{EX}/reshape"),
          _op(3127, 500, f"{STEP}/obs:grad/dot_general"),
          _op(3627, 80, "", "dynamic-update-slice")]


@pytest.mark.parametrize("metric, ns", [
    ("compress_ms", 2600), ("pack_ms", 100), ("ef_update_ms", 400),
    ("mix_ms", 20), ("unscoped_ms", 80)])
def test_stage_readers(metric, ns):
    # two steps: each reads its stage's time over the steps, in ms
    assert trace.read_metric(metric, _ctx(SCOPED)) == ns / 2 / 1e6


def test_compress_roofline_reads_the_stage():
    cell = cells.load("qwen3-1.7b.topk.1chip")
    got = trace.read_metric("compress_roofline", _ctx(SCOPED, cell=cell))
    least = counts.topk_bytes(cell.model, cell.traffic["fraction"]) \
        / peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    assert got == pytest.approx(100.0 * least / (2600 / 2 / 1e9))


def test_stages_and_the_rest_cover_the_exchange():
    by_stage = sum(trace.per_step_max(_ctx(SCOPED).reduced,
                                      stages.in_stage(s)) or 0.0
                   for s in stages.STAGES)
    exchange = trace.read_metric("exchange_ms", _ctx(SCOPED))
    assert by_stage == pytest.approx(exchange - 7 / 2 / 1e6)
    assert trace.per_step_max(_ctx(SCOPED).reduced, stages.unscoped) == \
        80 / 2 / 1e6


@pytest.mark.parametrize("metric", ["compress_ms", "compress_roofline",
                                    "pack_ms", "ef_update_ms", "mix_ms"])
def test_no_stage_scopes_read_nothing(metric):
    unstaged = [_op(0, 50, f"{EX}/jit(block_topk_select)/top_k", "sort"),
                _op(50, 5, f"{EX}/reshape"), _op(55, 5, "", "copy")]
    cell = cells.load("qwen3-1.7b.topk.1chip")
    assert trace.read_metric(metric, _ctx(unstaged, cell=cell)) is None


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(DATA, "qwen3-1.7b.topk.1chip.hlo.txt.gz"),
                   "rt") as f:
        hlo = f.read()
    red = trace.reduce_file(
        os.path.join(DATA, "qwen3-1.7b.topk.1chip.xplane.pb.gz"), hlo)
    return trace.Context(cell=cells.load("qwen3-1.7b.topk.1chip"),
                         reduced=red, tokens_per_s=2156.0,
                         device_kind="TPU v5 lite")


@pytest.mark.parametrize("metric", ["compress_ms", "compress_roofline",
                                    "pack_ms", "ef_update_ms", "mix_ms"])
def test_recorded_trace_has_no_stages(recorded, metric):
    """The recorded run's program opened no stage scopes."""
    assert trace.read_metric(metric, recorded) is None


def test_recorded_trace_unscoped_time(recorded):
    """XLA's sunk row-copy loop, the densify scatter and copies: 75.6 ms
    of qwen's step under no scope (TPU v5 lite, the recorded run)."""
    ms = trace.per_step_max(recorded.reduced, stages.unscoped)
    assert ms == pytest.approx(75.6, abs=0.1)


def test_unscoped_reader_on_the_recorded_trace(recorded):
    """``unscoped_ms`` reads the time no scope claims: 75.63 ms per step of
    the recorded run (TPU v5 lite)."""
    assert trace.read_metric("unscoped_ms", recorded) == \
        pytest.approx(75.62938833333332, rel=1e-12)
