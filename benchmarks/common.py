"""Shared benchmark utilities: timing + CSV emission + roofline constants +
the CPU child process the HLO audits run in."""
import os
import subprocess
import sys
import time

import jax

#: HBM bandwidth (bytes/s) every roofline-derived column is computed
#: against.  Single source of truth is repro.analysis.roofline (v5e figure,
#: REPRO_HBM_BW overrides) — re-exported here so benchmarks keep their
#: one-import habit.
from repro.analysis.roofline import HBM_BW  # noqa: E402


def time_fn(fn, *args, iters=3, warmup=1, **kw):
    """Median wall time in microseconds (blocks on device results)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args, **kw))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kw))
        ts.append((time.perf_counter() - t0) * 1e6)
    ts.sort()
    return ts[len(ts) // 2]


def emit(name, us_per_call, derived):
    print(f"{name},{us_per_call:.1f},{derived}")


SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_cpu_child(name, script, *, timeout=1800):
    """Run an HLO-audit ``script`` in a child process and return its stdout.

    The audits count ops in CPU-compiled HLO by design, so the child is
    pinned to the CPU (``JAX_PLATFORMS=cpu``) and never contends for an
    accelerator the parent may hold; it sets its own host device count.
    A failed child ends the bench with a nonzero exit."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=SRC + os.pathsep + os.path.join(SRC, ".."))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=timeout)
    if r.returncode != 0:
        raise SystemExit(f"{name}: audit child exited {r.returncode}\n"
                         f"{r.stderr[-2000:]}")
    return r.stdout
