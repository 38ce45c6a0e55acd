"""Telemetry subsystem acceptance (obs/).

Fast tier:
  * schema — record validation against the metric registry (reserved
    keys, unregistered keys, non-scalar values);
  * sinks — JSONL/stdout writers, the background MetricLog drains on
    close, validation errors surface at the emit call site;
  * divergence monitor — convergence-floor wobble never trips, sustained
    Lyapunov growth does;
  * timers — compile-aware tap accounting under a fake clock,
    nearest-rank percentiles;
  * Lyapunov contraction — CHOCO-GOSSIP under the Theorem-2 gamma
    contracts Xi_t = consensus + EF residual monotonically and at least
    at the (1 - delta^2 omega / 82)^t rate band on ring and hypercube;
    an overscaled gamma diverges and trips the monitor (the negative
    control the --divergence-action flag exists for).

Slow/distributed tier: the train launcher end-to-end with --diag-every
and --metrics-dir emits a JSONL run log in which every record validates
against the registry (header + compile-once + steady-state taps + diag
records).
"""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.choco_gossip import (auto_stepsize,
                                     choco_gossip_round_efficient,
                                     init_efficient_state, theorem2_rate)
from repro.core.compression import make_compressor
from repro.core.topology import make_topology
from repro.obs.schema import METRIC_SPECS, METRICS, validate_record
from repro.obs.sinks import (DivergenceMonitor, JsonlSink,
                             MetricLog, StdoutSink)
from repro.obs.timers import StepTimer, percentile
from repro.obs.trace import ProfileSession, annotate

from test_distributed import run_sub


# --------------------------------------------------------------------------
# schema
# --------------------------------------------------------------------------

def test_registry_entries_are_well_formed():
    names = [m.name for m in METRIC_SPECS]
    assert len(names) == len(set(names))
    for m in METRIC_SPECS:
        assert "/" in m.name and m.units.strip() and m.description.strip()
    assert METRICS["train/loss"].units == "nats"


def test_validate_record_accepts_registered_metrics():
    validate_record({"kind": "metrics", "step": 3, "train/loss": 1.5,
                     "extra": {"anything": "goes"}})
    validate_record({"kind": "header", "whatever": [1, 2]})
    validate_record({"kind": "log", "msg": "hello"})


def test_validate_record_rejects_bad_records():
    with pytest.raises(ValueError, match="kind"):
        validate_record({"kind": "nope"})
    with pytest.raises(ValueError, match="int step"):
        validate_record({"kind": "metrics", "step": True})
    with pytest.raises(ValueError, match="unregistered"):
        validate_record({"kind": "metrics", "step": 1, "train/bogus": 1.0})
    with pytest.raises(ValueError, match="scalar"):
        validate_record({"kind": "metrics", "step": 1,
                         "train/loss": [1.0, 2.0]})


# --------------------------------------------------------------------------
# sinks
# --------------------------------------------------------------------------

def test_metric_log_drains_to_jsonl_on_close(tmp_path):
    path = str(tmp_path / "m.jsonl")
    with MetricLog([JsonlSink(path)]) as mlog:
        mlog.header(arch="t", gamma=0.5)
        for i in range(20):
            mlog.emit(i, {"train/loss": float(i)})
        mlog.log("done")
    recs = [json.loads(line) for line in open(path)]
    assert [r["kind"] for r in recs] == (["header"] + ["metrics"] * 20
                                         + ["log"])
    for r in recs:
        validate_record(r)
    assert recs[1]["train/loss"] == 0.0 and recs[-2]["step"] == 19


def test_metric_log_validates_on_calling_thread(tmp_path):
    mlog = MetricLog([JsonlSink(str(tmp_path / "m.jsonl"))])
    try:
        with pytest.raises(ValueError, match="unregistered"):
            mlog.emit(0, {"train/nonsense": 1.0})
    finally:
        mlog.close()


def test_stdout_sink_formatter_skips_none(capsys):
    fmt = lambda rec: None if rec["kind"] == "header" else "LINE"
    with MetricLog([StdoutSink(formatter=fmt)]) as mlog:
        mlog.header(hidden=1)
        mlog.log("shown")
    out = capsys.readouterr().out
    assert "LINE" in out and "hidden" not in out


def test_divergence_monitor_tolerates_floor_wobble():
    mon = DivergenceMonitor(tolerance=1.05, patience=3)
    xi = 100.0
    for step in range(40):
        xi *= 0.9
        assert mon.update(step, xi) is None
    # wobble around the floor within tolerance: never trips
    floor = xi
    for step in range(40, 60):
        assert mon.update(step, floor * (1.0 + 0.02 * (step % 2))) is None
    assert not mon.tripped


def test_divergence_monitor_trips_on_sustained_growth():
    mon = DivergenceMonitor(tolerance=1.05, patience=3)
    assert mon.update(0, 100.0) is None
    msgs = [mon.update(s, 100.0 * 1.3 ** s) for s in range(1, 5)]
    tripped = [m for m in msgs if m is not None]
    assert tripped and mon.tripped
    assert "gamma" in tripped[0] and "Lyapunov" in tripped[0]


# --------------------------------------------------------------------------
# timers / trace
# --------------------------------------------------------------------------

def test_step_timer_separates_compile_from_steady_state():
    clock = iter([0.0, 10.0, 18.0, 20.0]).__next__
    timer = StepTimer(clock=clock)
    timer.start()                                   # t=0
    compile_s = timer.mark_compile(lambda: None)    # t=10
    assert compile_s == 10.0 and timer.compile_s == 10.0
    # steps 1..4 done by t=18: 8s over 4 steps
    assert timer.tap(4, lambda: None) == pytest.approx(2.0)
    # no new steps since the tap: no blocking, no sample
    assert timer.tap(4, lambda: None) is None
    # one more step by t=20
    assert timer.tap(5, lambda: None) == pytest.approx(2.0)


def test_step_timer_requires_start():
    with pytest.raises(ValueError, match="start"):
        StepTimer().mark_compile(lambda: None)
    with pytest.raises(ValueError, match="start"):
        StepTimer().tap(0, lambda: None)


def test_percentile_nearest_rank():
    vals = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert percentile(vals, 50) == 3.0
    assert percentile(vals, 99) == 5.0
    assert percentile(vals, 0) == 1.0
    assert percentile([7.0], 50) == 7.0
    with pytest.raises(ValueError):
        percentile([], 50)
    with pytest.raises(ValueError):
        percentile([1.0], 101)


def test_profile_session_noop_without_dir(tmp_path):
    prof = ProfileSession(None)
    assert not prof.maybe_start(1) and not prof.maybe_stop(1)
    prof.close()
    assert not prof.active and not prof.done
    with pytest.raises(ValueError, match="n_steps"):
        ProfileSession(str(tmp_path), n_steps=0)
    with annotate("obs:test"):      # degrades to a no-op context
        pass


@pytest.mark.slow
def test_launcher_profile_window_records_host_spans(tmp_path):
    """Under --profile-dir the trace holds the loop's host phases as
    spans, on the device trace's clock."""
    import glob
    from jax.profiler import ProfileData
    from repro.launch.train import main
    prof = str(tmp_path / "prof")
    assert main(["--arch", "qwen3-1.7b", "--smoke", "--seq-len", "16",
                 "--batch-per-node", "1", "--steps", "4",
                 "--compressor", "top_k", "--fraction", "0.05",
                 "--diag-every", "1",
                 "--checkpoint-dir", str(tmp_path / "ckpt"),
                 "--checkpoint-every", "3",
                 "--profile-dir", prof, "--profile-steps", "3"]) == 0
    found = glob.glob(os.path.join(prof, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(found) == 1, found
    names = {e.name for plane in ProfileData.from_file(found[0]).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    # traced steps 1..3: input, dispatch and diag each step, the
    # checkpoint after step 2, the tap on the last step
    assert {"train:input", "train:dispatch", "train:tap", "train:diag",
            "train:checkpoint"} <= names


# --------------------------------------------------------------------------
# Lyapunov contraction (the quantity --diag-every reports)
# --------------------------------------------------------------------------

def _xi_trace(topo_name, gamma, rounds, seed=0):
    """Xi_t per CHOCO-GOSSIP round on the (n, d) matrix simulator."""
    n, d = 8, 64
    topo = make_topology(topo_name, n)
    comp = make_compressor("top_k", fraction=0.25)
    W = jnp.asarray(topo.W, jnp.float32)
    x0 = jax.random.normal(jax.random.PRNGKey(seed), (n, d))
    xbar = jnp.mean(x0, axis=0, keepdims=True)
    st = init_efficient_state(x0)

    def xi(s):
        return float(jnp.sum((s.x - xbar) ** 2)
                     + jnp.sum((s.x - s.x_hat) ** 2))

    trace = [xi(st)]
    key = jax.random.PRNGKey(seed + 1)
    for t in range(rounds):
        st = choco_gossip_round_efficient(st, W, gamma, comp,
                                          jax.random.fold_in(key, t))
        trace.append(xi(st))
    return topo, comp, trace


@pytest.mark.parametrize("topo_name", ["ring", "hypercube"])
def test_lyapunov_contracts_at_theorem2_rate(topo_name):
    n, d, rounds = 8, 64, 300
    topo = make_topology(topo_name, n)
    comp = make_compressor("top_k", fraction=0.25)
    gamma = auto_stepsize(topo, comp, d)
    topo, comp, trace = _xi_trace(topo_name, gamma, rounds)
    rate = theorem2_rate(topo.delta, comp.omega(d))
    # at least as fast as the Theorem-2 band, and a genuine contraction
    assert trace[-1] <= trace[0] * rate ** rounds, (trace[-1], trace[0])
    assert trace[-1] < 0.5 * trace[0]
    # monotone: the deterministic top-k path never moves Xi_t up
    for a, b in zip(trace, trace[1:]):
        assert b <= a + 1e-4 * trace[0], (a, b)
    # the divergence monitor stays quiet on a healthy run
    mon = DivergenceMonitor()
    assert all(mon.update(t, v) is None for t, v in enumerate(trace))


def test_overscaled_gamma_diverges_and_trips_monitor():
    # ~2000x the Theorem-2 gamma: the error-feedback loop overshoots and
    # Xi_t grows without bound — the failure mode --divergence-action
    # exists to catch
    _, _, trace = _xi_trace("ring", 2.0, 30)
    assert trace[-1] > 10 * trace[0]
    mon = DivergenceMonitor()
    msgs = [mon.update(t, v) for t, v in enumerate(trace)]
    assert mon.tripped and any(m is not None for m in msgs)


# --------------------------------------------------------------------------
# end-to-end: launcher -> validated JSONL run log (slow/distributed)
# --------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.distributed
def test_train_launcher_emits_validated_run_log(tmp_path):
    mdir = str(tmp_path / "metrics")
    run_sub(f"""
        import json
        from repro.launch.train import main
        from repro.obs.schema import validate_record

        mdir = {mdir!r}
        assert main(["--arch", "qwen3-1.7b", "--smoke", "--mesh", "8x1",
                     "--simulate-devices", "8", "--seq-len", "32",
                     "--batch-per-node", "2", "--steps", "5",
                     "--compressor", "top_k", "--fraction", "0.05",
                     "--diag-every", "2", "--metrics-dir", mdir,
                     "--divergence-action", "warn"]) == 0
        recs = [json.loads(l) for l in open(mdir + "/metrics.jsonl")]
        for r in recs:
            validate_record(r)      # every record passes the registry

        headers = [r for r in recs if r["kind"] == "header"]
        assert len(headers) == 1
        h = headers[0]
        assert h["jax_version"] and h["mesh"] == {{"data": 8, "model": 1}}
        assert h["fingerprint"]["compressor"] == "top_k"
        assert h["gamma"] > 0 and h["wire_bytes_round"] > 0
        assert h["buckets"] and all("omega" in b for b in h["buckets"])
        assert h["kernel_backend"] == "jnp"     # auto off the chip
        assert h["reduced"] is None             # --smoke, no depth cut
        assert h["node_devices"] == [[i] for i in range(8)]

        mets = [r for r in recs if r["kind"] == "metrics"]
        compile_recs = [r for r in mets if "train/compile_s" in r]
        assert len(compile_recs) == 1          # compile reported once
        assert "train/s_per_step" not in compile_recs[0]
        assert any("train/s_per_step" in r for r in mets)
        diags = [r for r in mets if "diag/lyapunov" in r]
        assert [r["step"] for r in diags] == [2, 4]
        for r in diags:
            assert r["diag/consensus_dist"] >= 0
            assert r["diag/compress_err"] <= r["diag/compress_err_bound"]
        print("RUN LOG OK")
    """)
