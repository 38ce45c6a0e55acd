"""Structured metric sinks: registered-schema records to JSONL / stdout,
written off the hot loop by a background thread.

Design (docs/ARCHITECTURE.md §Observability):

* records are plain dicts validated against ``obs/schema.py`` **on the
  emitting thread** — a typo'd key raises at the call site, never inside
  the writer thread;
* the writer thread owns all file/stdout I/O, so a tap-step emit costs
  one queue put (the training loop never blocks on a disk flush);
* the stdout sink takes a formatter so the launchers keep their
  historical line formats byte-for-byte while still flowing through the
  sink (``kind="log"`` records render their ``msg`` verbatim);
* :class:`DivergenceMonitor` watches the logged Lyapunov series Xi_t and
  warns/aborts when it stops contracting — the runtime counterpart of
  the Theorem-2 linear-contraction test in ``tests/test_obs.py``.

Module is jax-free at import (launchers import it pre-XLA_FLAGS) and is
host-side by design: it is on the traced-purity exemption list, unlike
``obs/metrics.py``.
"""
from __future__ import annotations

import json
import os
import queue
import threading
from typing import Callable, List, Optional, Sequence

from repro.obs.schema import validate_record


class Sink:
    """Destination for validated records; subclasses own one output."""

    def write(self, record: dict) -> None:
        """Consume one validated record."""
        raise NotImplementedError

    def close(self) -> None:
        """Flush and release the output (idempotent)."""


class StdoutSink(Sink):
    """Print records to stdout through a caller-supplied formatter.

    ``formatter(record)`` returns the line to print, or ``None`` to skip
    the record on stdout (e.g. the train launcher prints step lines and
    log lines but keeps header records file-only).  Default formatter:
    ``msg`` verbatim for log records, compact JSON otherwise.
    """

    def __init__(self, formatter: Optional[Callable[[dict],
                                                    Optional[str]]] = None):
        self._format = formatter or self._default

    @staticmethod
    def _default(record: dict) -> str:
        if record.get("kind") == "log":
            return str(record.get("msg", ""))
        return json.dumps(record, sort_keys=True)

    def write(self, record: dict) -> None:
        """Format and print one record (flushes: lines must interleave
        correctly with subprocess capture)."""
        line = self._format(record)
        if line is not None:
            print(line, flush=True)


class JsonlSink(Sink):
    """One JSON object per line; the machine-readable run log."""

    def __init__(self, path: str):
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self._f = open(path, "a", encoding="utf-8")

    def write(self, record: dict) -> None:
        """Append one record as a JSON line."""
        self._f.write(json.dumps(record, sort_keys=True) + "\n")

    def close(self) -> None:
        """Flush and close the file (idempotent)."""
        if not self._f.closed:
            self._f.flush()
            self._f.close()


class MetricLog:
    """Validating front end + non-blocking background writer for a set of
    sinks.

    ``emit``/``header``/``log`` validate on the calling thread, then hand
    the record to a daemon writer thread; ``close()`` drains the queue and
    closes every sink.  Usable as a context manager.
    """

    def __init__(self, sinks: Sequence[Sink]):
        self._sinks: List[Sink] = list(sinks)
        self._q: "queue.Queue[Optional[dict]]" = queue.Queue()
        self._thread = threading.Thread(target=self._drain, daemon=True,
                                        name="obs-metric-writer")
        self._thread.start()
        self._closed = False

    def _drain(self) -> None:
        while True:
            record = self._q.get()
            if record is None:
                return
            for sink in self._sinks:
                sink.write(record)

    def write(self, record: dict) -> None:
        """Validate and enqueue one raw record."""
        if self._closed:
            raise ValueError("MetricLog is closed")
        self._q.put(validate_record(dict(record)))

    def header(self, **fields) -> None:
        """Emit the run-header record (config fingerprint, jax version,
        mesh, resolved gamma, ...)."""
        self.write({"kind": "header", **fields})

    def emit(self, step: int, metrics: dict,
             extra: Optional[dict] = None) -> None:
        """Emit one metrics record at ``step``; unregistered keys raise
        here, at the call site."""
        record = {"kind": "metrics", "step": int(step), **metrics}
        if extra:
            record["extra"] = extra
        self.write(record)

    def log(self, msg: str) -> None:
        """Emit a log record (rendered verbatim by the stdout sink)."""
        self.write({"kind": "log", "msg": msg})

    def close(self) -> None:
        """Drain the queue, stop the writer, close every sink."""
        if self._closed:
            return
        self._closed = True
        self._q.put(None)
        self._thread.join(timeout=30.0)
        for sink in self._sinks:
            sink.close()

    def __enter__(self) -> "MetricLog":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class DivergenceMonitor:
    """Trips when the Lyapunov series Xi_t stops contracting.

    Theorem 2 guarantees E[Xi_{t+1}] <= (1 - delta^2 omega / 82) Xi_t
    under the derived gamma, so a healthy run keeps making new bests and
    any excursion above ``tolerance * best`` is transient.  The monitor
    trips when Xi exceeds that band for ``patience`` consecutive
    observations — wobble at the numerical convergence floor stays inside
    the band and never false-positives.  ``update`` returns the warning
    string once, at the trip; ``tripped`` stays set so the caller decides
    warn-vs-abort.
    """

    def __init__(self, tolerance: float = 1.05, patience: int = 3):
        if tolerance < 1.0 or patience < 1:
            raise ValueError(f"need tolerance >= 1 and patience >= 1, got "
                             f"{tolerance}, {patience}")
        self.tolerance = float(tolerance)
        self.patience = int(patience)
        self.best: Optional[float] = None
        self.streak = 0
        self.tripped = False

    def update(self, step: int, xi: float) -> Optional[str]:
        """Observe Xi at ``step``; returns the trip message, or None."""
        xi = float(xi)
        if self.best is None or xi < self.best:
            self.best, self.streak = xi, 0
            return None
        if xi <= self.tolerance * self.best:
            self.streak = 0          # contracting-enough band: not a sign
            return None
        self.streak += 1
        if self.streak < self.patience or self.tripped:
            return None
        self.tripped = True
        return (f"divergence monitor tripped at step {step}: Lyapunov "
                f"Xi = {xi:.3e} has stayed above {self.tolerance:g}x the "
                f"best {self.best:.3e} for {self.streak} consecutive "
                f"observations — Theorem 2 demands linear contraction "
                f"under the derived gamma; check for an overscaled "
                f"--consensus-gamma or a mis-tuned compressor")
