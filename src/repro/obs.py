"""Spans, scopes and counters at the simulator's layer boundaries.

- ``span(name)`` marks host work. It opens a
  ``jax.profiler.TraceAnnotation`` named exactly ``name``, so a profiler
  trace shows it on the same clock as the device ops, and adds to the
  totals ``<name>.calls`` and ``<name>.seconds`` (host seconds,
  ``time.perf_counter``).
- ``count(name, n)`` adds ``n`` to the total ``name``.
- ``scope(name)`` names the device ops traced inside it: a
  ``jax.named_scope``, so their ``op_name`` metadata holds ``name/``.
- ``upload(x, dtype)`` sends a host array to the device and counts its
  bytes as sent; ``fetch(x)`` brings a device result to the host inside
  an ``exec.sync`` span.

Totals are kept for the whole process (``totals()``) and for each
``RoundEngine.run`` (``run()``; ``last_run()`` is the last one that
finished, also returned as ``SimResult.counters``). There is no switch:
with the profiler off a ``TraceAnnotation`` costs next to nothing, and
the spans are per block or tick.

Names in use (``PERF.md`` lists the metric each feeds):

- spans: ``engine.build``, ``sim.plan``, ``exec.build``,
  ``exec.dispatch``, ``exec.sync``, ``sim.eval``;
- counters: ``exec.dispatches``, ``exec.upload_bytes``, ``updates``;
- device scopes: ``train``, ``fold``, ``eval``.
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np


class _Totals:
    """The process's totals and those of the run in progress."""

    def __init__(self):
        self.process: dict[str, float] = {}
        self.run: Optional[dict[str, float]] = None
        self.last_run: dict[str, float] = {}

    def add(self, name: str, n: float) -> None:
        self.process[name] = self.process.get(name, 0) + n
        if self.run is not None:
            self.run[name] = self.run.get(name, 0) + n


_TOTALS = _Totals()


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to the counter ``name``."""
    _TOTALS.add(name, n)


@contextlib.contextmanager
def span(name: str) -> Iterator[None]:
    """A host span: a profiler event named ``name``, and its call count
    and host seconds added to the totals."""
    t = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        _TOTALS.add(f"{name}.calls", 1)
        _TOTALS.add(f"{name}.seconds", time.perf_counter() - t)


def scope(name: str):
    """A device scope: ops traced inside carry ``name/`` in their
    ``op_name`` metadata."""
    return jax.named_scope(name)


def upload(x: Any, dtype: Any = None) -> jax.Array:
    """Send a host array to the device, counting its bytes as sent.

    The cast happens in numpy first (to ``dtype``, else to the dtype JAX
    would give it), so the device copy is dtype-preserving: a *casting*
    ``jnp.asarray(x, dtype)`` counts as an implicit transfer under
    ``jax.transfer_guard``, which ``repro.debug.sanitize`` runs the
    block loop with."""
    a = np.asarray(x)
    a = np.asarray(a, jax.dtypes.canonicalize_dtype(
        a.dtype if dtype is None else dtype))
    count("exec.upload_bytes", a.nbytes)
    return jnp.asarray(a)


def fetch(x: Any) -> np.ndarray:
    """Wait for a device result and copy it to the host."""
    with span("exec.sync"):
        return np.asarray(x)


@contextlib.contextmanager
def run() -> Iterator[dict[str, float]]:
    """Fresh totals for one run; they become ``last_run()`` when it ends
    without an error."""
    outer, _TOTALS.run = _TOTALS.run, {}
    try:
        yield _TOTALS.run
        _TOTALS.last_run = _TOTALS.run
    finally:
        _TOTALS.run = outer


def totals() -> dict[str, float]:
    """The process's totals so far."""
    return dict(_TOTALS.process)


def last_run() -> dict[str, float]:
    """The totals of the last run that finished."""
    return dict(_TOTALS.last_run)


__all__ = ["count", "fetch", "last_run", "run", "scope", "span", "totals",
           "upload"]
