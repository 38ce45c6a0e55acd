"""jax-free process-environment helpers (safe to import before XLA_FLAGS
is frozen by the first jax import): simulated host devices and the home of
the persistent compile cache."""
from __future__ import annotations

import os

_DEVICE_COUNT_FLAG = "--xla_force_host_platform_device_count"


def simulate_host_devices(n: int) -> str:
    """Request ``n`` simulated host devices by APPENDING to XLA_FLAGS.

    Never clobbers pre-set flags (a user's --xla_dump_to etc. must survive
    --simulate-devices); any pre-existing device-count flag is replaced by
    ours, since XLA's last-wins duplicate handling is not a contract worth
    leaning on.  Must be called before jax is imported."""
    keep = [t for t in os.environ.get("XLA_FLAGS", "").split()
            if not t.startswith(_DEVICE_COUNT_FLAG + "=")]
    keep.append(f"{_DEVICE_COUNT_FLAG}={int(n)}")
    os.environ["XLA_FLAGS"] = " ".join(keep)
    return os.environ["XLA_FLAGS"]


#: where the compile cache lives when ``JAX_COMPILATION_CACHE_DIR`` is unset:
#: one fixed directory inside the checkout (gitignored), so every run of
#: this checkout finds what an earlier run compiled
DEFAULT_COMPILE_CACHE = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def place_compile_cache():
    """Point JAX's persistent compilation cache at its one home; call before
    the first compile.  With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already
    reads it and nothing is changed.  Otherwise, on a TPU, the cache goes
    to :data:`DEFAULT_COMPILE_CACHE`; off the chip nothing is placed (CPU
    compiles are cheap, and XLA:CPU's cached executables are tied to the
    compiling host's instruction set).  Returns the directory in use, or
    None."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    if jax.default_backend() != "tpu":
        return None
    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE)
    return DEFAULT_COMPILE_CACHE
