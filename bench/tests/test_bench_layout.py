"""The benchmark is driven by data: every name in BENCHMARK.json has its
files, and the harness refuses to run where it cannot measure."""
import importlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["paths"] == ["bench"]
    assert spec["command"] == ["python3", "bench/run.py"]
    assert isinstance(spec["run_seconds"], int) \
        and 1 <= spec["run_seconds"] <= 51


def test_every_cell_has_its_files(spec):
    configs = {c["name"]: c for c in spec["configs"]}
    used = set()
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        c = configs[w["config"]]
        used.add(c["name"])
        with open(os.path.join(ROOT, c["file"])) as f:
            config = json.load(f)
        assert config["name"] == c["name"]
        assert config["source"] == c["source"]
        assert sorted(config["reduced"]) == sorted(c["reduced"])
        with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
            assert json.load(f)["nodes"] == w["chips"]
        with open(os.path.join(BENCH, "limits", w["name"] + ".json")) as f:
            assert json.load(f)
    assert used == set(configs)
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_per_layer_metric_has_a_reader(spec):
    cells = {w["name"] for w in spec["workloads"]}
    e2e = {m["name"] for m in spec["end_to_end"]}
    for m in spec["per_layer"]:
        reader = importlib.import_module(f"bench.metrics.{m['name']}")
        assert callable(reader.read)
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells


def test_no_file_without_its_name(spec):
    """Every reader, traffic mix and limits file belongs to a declared
    metric or cell: nothing inert ships under bench/."""
    def stems(sub, ext):
        return {f[:-len(ext)] for f in os.listdir(os.path.join(BENCH, sub))
                if f.endswith(ext) and not f.startswith("_")}
    assert stems("metrics", ".py") == {m["name"] for m in spec["per_layer"]}
    assert stems("traffic", ".json") == {w["traffic"]
                                         for w in spec["workloads"]}
    assert stems("limits", ".json") == {w["name"] for w in spec["workloads"]}
    assert stems("configs", ".json") == {c["name"] for c in spec["configs"]}


def test_names_and_units(spec):
    names = [c["name"] for c in spec["configs"]]
    names += [w["name"] for w in spec["workloads"]]
    names += [w["traffic"] for w in spec["workloads"]]
    names += [k for c in spec["configs"] for k in c["reduced"]]
    metrics = spec["end_to_end"] + spec["per_layer"]
    names += [m["name"] for m in metrics]
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert "bound" not in m


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen3-1.7b.topk.1chip", "--seed", "3000000001", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_tpu_no_result():
    proc = _run(ROOT, {})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_without_the_program_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, {})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
