"""Profiler hooks: phase annotations and TensorBoard trace capture.

Two layers, both jax-free at import (jax is pulled in lazily so the
launchers can import this module before XLA_FLAGS is frozen):

* :func:`annotate` — host-side ``jax.profiler.TraceAnnotation`` context
  manager around launcher phases (dispatch, checkpoint save, decode
  request); a no-op string context when profiling machinery is absent.
* :class:`ProfileSession` — drives ``jax.profiler.start_trace`` /
  ``stop_trace`` over a step window (``--profile-dir`` +
  ``--profile-steps``), skipping the compiling first step so the trace
  shows steady state, and emitting a TensorBoard-loadable trace dir.

In-graph phase names (exchange/grad/optimizer, and the exchange's stages
from ``comm/stages.py``) come from ``jax.named_scope`` and are only
enabled under ``phase_scopes=True`` — named scopes land in HLO metadata,
so the default path keeps the compiled train step byte-identical to the
pre-telemetry HLO (the ``telemetry_off`` invariant).
"""
from __future__ import annotations

import contextlib
from typing import Iterator, Optional


@contextlib.contextmanager
def annotate(name: str) -> Iterator[None]:
    """Host-side profiler annotation: wraps the block in a
    ``jax.profiler.TraceAnnotation`` so it shows as a named span in a
    captured trace; degrades to a no-op if the profiler is unavailable."""
    try:
        from jax.profiler import TraceAnnotation
    except Exception:   # pragma: no cover - profiler-less builds
        yield
        return
    with TraceAnnotation(name):
        yield


class ProfileSession:
    """Trace a window of steps into a TensorBoard-loadable directory.

    ``maybe_start(i)`` / ``maybe_stop(i)`` bracket the loop body:
    tracing starts before step ``start_step`` (default 1 — skip the
    compiling step 0) and stops after ``n_steps`` traced steps, with the
    caller expected to synchronize the device before ``maybe_stop`` so
    the trace covers real execution, not just dispatch.  A ``None``
    profile dir makes every method a no-op, so the launcher loop carries
    no conditionals.
    """

    def __init__(self, profile_dir: Optional[str], n_steps: int = 3,
                 start_step: int = 1):
        if profile_dir is not None and n_steps < 1:
            raise ValueError(f"need n_steps >= 1, got {n_steps}")
        self.profile_dir = profile_dir
        self.start_step = int(start_step)
        self.stop_after = int(start_step) + int(n_steps)
        self.active = False
        self.done = False

    def maybe_start(self, step_index: int) -> bool:
        """Start tracing when the window opens; returns True on start."""
        if (self.profile_dir is None or self.active or self.done
                or step_index != self.start_step):
            return False
        import jax
        jax.profiler.start_trace(self.profile_dir)
        self.active = True
        return True

    def maybe_stop(self, step_index: int) -> bool:
        """Stop tracing when the window closes (caller has synchronized);
        returns True on stop."""
        if not self.active or step_index + 1 < self.stop_after:
            return False
        import jax
        jax.profiler.stop_trace()
        self.active, self.done = False, True
        return True

    def close(self) -> None:
        """Stop an in-flight trace (loop ended inside the window)."""
        if self.active:
            import jax
            jax.profiler.stop_trace()
            self.active, self.done = False, True
