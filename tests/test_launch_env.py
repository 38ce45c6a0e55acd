"""Launcher environment: --simulate-devices must APPEND to XLA_FLAGS,
never clobber them; the compile cache has one home; meshes come from
launch/mesh.py; the --n-layers depth cut fails fast."""
import pytest

from repro.launch.env import simulate_host_devices


def test_appends_to_preset_flags(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--xla_dump_to=/tmp/dump --xla_foo=1")
    got = simulate_host_devices(8)
    toks = got.split()
    assert "--xla_dump_to=/tmp/dump" in toks
    assert "--xla_foo=1" in toks
    assert "--xla_force_host_platform_device_count=8" in toks


def test_replaces_stale_device_count(monkeypatch):
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=2 --xla_foo=1")
    toks = simulate_host_devices(8).split()
    assert toks.count("--xla_force_host_platform_device_count=8") == 1
    assert "--xla_force_host_platform_device_count=2" not in toks
    assert "--xla_foo=1" in toks


def test_unset_env(monkeypatch):
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    assert simulate_host_devices(4) == \
        "--xla_force_host_platform_device_count=4"


# -- compile cache placement -------------------------------------------------

def test_compile_cache_env_var_wins(monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set, the helper changes nothing."""
    import jax
    from repro.launch.env import place_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert place_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_on_tpu_is_one_fixed_path_in_checkout(monkeypatch):
    import os
    import jax
    from repro.launch import env
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = jax.config.jax_compilation_cache_dir
    try:
        got = env.place_compile_cache()
        assert got == env.DEFAULT_COMPILE_CACHE
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    assert got == os.path.join(root, ".jax_cache")
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_compile_cache_off_chip_places_nothing(monkeypatch):
    import jax
    from repro.launch.env import place_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    before = jax.config.jax_compilation_cache_dir
    assert place_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


# -- meshes --------------------------------------------------------------------

def test_default_mesh_makes_every_device_a_node():
    import jax
    from jax.sharding import AxisType
    from repro.launch.mesh import make_default_mesh
    mesh = make_default_mesh()
    assert dict(mesh.shape) == {"data": len(jax.devices()), "model": 1}
    assert set(mesh.axis_types) == {AxisType.Auto}


def test_make_mesh_takes_an_explicit_device_list():
    import jax
    from jax.sharding import AxisType
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    assert mesh.devices.ravel().tolist() == jax.devices()[:1]
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)


# -- --n-layers (depth cut, validated before jax) ------------------------------

@pytest.mark.parametrize("argv,msg", [
    (["--n-layers", "29"], "[1, 28]"),
    (["--n-layers", "0"], "[1, 28]"),
    (["--smoke", "--n-layers", "1"], "--smoke"),
])
def test_n_layers_fail_fast(argv, msg, capsys):
    from repro.launch.train import main
    with pytest.raises(SystemExit) as ei:
        main(["--arch", "qwen3-1.7b"] + argv)
    assert ei.value.code == 2
    assert msg in capsys.readouterr().err
