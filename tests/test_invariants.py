"""Engine-invariant registry tests (fast tier) + a live-compile check.

The registry (``repro.analysis.invariants.ENGINE_INVARIANTS``) is the
single statement of every structural claim a benchmark or test asserts
about a gossip engine's compiled form.  The fast tests pin the expression
evaluator's safety envelope, the lookup semantics, and the conformance of
the committed BENCH_*.json records; the slow test re-derives one registry
entry from a real 8-device compile so the registry can never drift from
the engines it describes.
"""
import json
import os
import textwrap

import pytest

from repro.analysis.findings import Finding
from repro.analysis.invariants import (CONTEXT_VARS, ENGINE_INVARIANTS,
                                       assert_invariant, check_invariant,
                                       evaluate_expectation, get_invariant,
                                       lint_bench_invariants)

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


# --------------------------------------------------------------------------
# registry + evaluator
# --------------------------------------------------------------------------

def test_registry_is_wellformed_and_repo_records_conform():
    findings = lint_bench_invariants(ROOT)
    assert findings == [], [f.render() for f in findings]


def test_every_registered_expression_evaluates():
    for inv in ENGINE_INVARIANTS:
        for metric, expr in inv.expect:
            assert isinstance(evaluate_expectation(expr), int), (inv, metric)


def test_get_invariant_backend_wildcard_and_miss():
    assert get_invariant("choco_serial", "pallas").backend == "pallas"
    # the pipelined entry is backend="*": any backend resolves to it
    assert get_invariant("choco_pipelined", "pallas").backend == "*"
    with pytest.raises(KeyError):
        get_invariant("no_such_engine", "jnp")


def test_evaluate_expectation_arithmetic_and_rejections():
    assert evaluate_expectation("2 * buckets * steps",
                                dict(CONTEXT_VARS, buckets=3, steps=2)) == 12
    assert evaluate_expectation("0") == 0
    with pytest.raises(ValueError):
        evaluate_expectation("unknown_name + 1")
    with pytest.raises(ValueError):
        evaluate_expectation("__import__('os').system('true')")


def test_check_invariant_skips_unmeasured_and_flags_mismatch():
    inv = get_invariant("choco_pipelined", "jnp")
    ctx = dict(CONTEXT_VARS, baseline=16)
    # only one of the two metrics measured: the other must be skipped
    assert check_invariant(inv, {"permute_launches": 16}, ctx) == []
    violations = check_invariant(
        inv, {"permute_launches": 20, "dots_feeding_collective": 0}, ctx)
    assert len(violations) == 1
    assert "permute_launches = 20" in violations[0]
    assert "expected baseline = 16" in violations[0]


def test_assert_invariant_raises_with_pointed_message():
    with pytest.raises(AssertionError, match="pallas_calls = 5"):
        assert_invariant("choco_serial", "pallas", {"pallas_calls": 5},
                         dict(CONTEXT_VARS, buckets=2, steps=1))
    # the happy path is silent
    assert_invariant("choco_serial", "pallas", {"pallas_calls": 4},
                     dict(CONTEXT_VARS, buckets=2, steps=1))


# --------------------------------------------------------------------------
# doctored-record detection (the invariant lint pass's whole point)
# --------------------------------------------------------------------------

def _scratch_with_bench(tmp_path, overlap=None, fused=None):
    if overlap is not None:
        (tmp_path / "BENCH_overlap.json").write_text(json.dumps(overlap))
    if fused is not None:
        (tmp_path / "BENCH_fused.json").write_text(json.dumps(fused))
    return str(tmp_path)


def test_doctored_pipelined_gating_is_flagged(tmp_path):
    rec = {"serial": {"permute_launches": 16, "dots_total": 30,
                      "dots_feeding_collective": 30},
           "pipelined": {"permute_launches": 16, "dots_total": 30,
                         "dots_feeding_collective": 5}}
    findings = lint_bench_invariants(_scratch_with_bench(tmp_path, rec))
    assert len(findings) == 1 and isinstance(findings[0], Finding)
    assert findings[0].path == "BENCH_overlap.json"
    assert "dots_feeding_collective = 5" in findings[0].message


def test_doctored_permute_parity_is_flagged(tmp_path):
    rec = {"serial": {"permute_launches": 16, "dots_total": 30,
                      "dots_feeding_collective": 30},
           "pipelined": {"permute_launches": 24, "dots_total": 30,
                         "dots_feeding_collective": 0}}
    findings = lint_bench_invariants(_scratch_with_bench(tmp_path, rec))
    assert len(findings) == 1
    assert "permute_launches = 24" in findings[0].message
    assert "expected baseline = 16" in findings[0].message


def test_doctored_pallas_launch_count_is_flagged(tmp_path):
    rec = {"pallas": {"n_buckets": 2, "pallas_calls": 6}}
    findings = lint_bench_invariants(_scratch_with_bench(tmp_path,
                                                         fused=rec))
    assert len(findings) == 1
    assert findings[0].path == "BENCH_fused.json"
    assert "pallas_calls = 6" in findings[0].message
    assert "expected 2 * buckets * steps = 4" in findings[0].message


def test_doctored_straggler_parity_is_flagged(tmp_path):
    rec = {"straggler": {"global_staleness": 8, "straggler_staleness": 12}}
    (tmp_path / "BENCH_scenarios.json").write_text(json.dumps(rec))
    findings = lint_bench_invariants(str(tmp_path))
    assert len(findings) == 1
    assert findings[0].path == "BENCH_scenarios.json"
    assert "permute_launches = 12" in findings[0].message
    assert "expected baseline = 8" in findings[0].message
    # the committed-record shape passes
    rec["straggler"]["straggler_staleness"] = 8
    (tmp_path / "BENCH_scenarios.json").write_text(json.dumps(rec))
    assert lint_bench_invariants(str(tmp_path)) == []


def test_clean_scratch_records_pass(tmp_path):
    overlap = {"serial": {"permute_launches": 8, "dots_total": 12,
                          "dots_feeding_collective": 12},
               "pipelined": {"permute_launches": 8, "dots_total": 12,
                             "dots_feeding_collective": 0}}
    fused = {"pallas": {"n_buckets": 3, "pallas_calls": 6}}
    assert lint_bench_invariants(
        _scratch_with_bench(tmp_path, overlap, fused)) == []


# --------------------------------------------------------------------------
# live compile: one registry entry re-derived from a real engine
# --------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.distributed
def test_registry_matches_live_pipelined_compile():
    """The choco_pipelined registry entry holds on a real 8-device compile
    of the gossip exchange — the registry cannot drift from the engine."""
    from tests.test_pipelined import run_sub
    run_sub(textwrap.dedent("""
        from jax.sharding import PartitionSpec as P
        from repro.comm.gossip import make_gossip_exchange
        from repro.core import TopK
        from repro.analysis.hlo_audit import collective_dependency_audit
        from repro.analysis.invariants import CONTEXT_VARS, assert_invariant

        n, d = 8, 512
        mesh = make_mesh((8, 1), ("data", "model"))
        x0 = jax.random.normal(jax.random.PRNGKey(0), (n, d))
        z = jnp.zeros_like(x0)
        audits = {}
        for pipe in (False, True):
            ex = make_gossip_exchange(mode="choco", mesh=mesh,
                                      state_specs=P("data", None),
                                      axis="data", compressor=TopK(k=64),
                                      gamma=0.3, pipelined=pipe)
            hlo = jax.jit(ex).lower(jax.random.PRNGKey(1), x0, z,
                                    z).compile().as_text()
            audits[pipe] = collective_dependency_audit(hlo).as_dict()
        ctx = dict(CONTEXT_VARS, baseline=audits[False]["permute_launches"])
        assert_invariant("choco_pipelined", "jnp",
                         {"dots_feeding_collective":
                          audits[True]["dots_feeding_collective"],
                          "permute_launches":
                          audits[True]["permute_launches"]}, ctx)
        print("REGISTRY-LIVE OK", audits)
    """))
