"""Program spans and counters, on the profiler's clock.

:func:`span` marks a phase of the read window or the write fence.  While
tracing is off (the default) it returns one shared no-op context manager;
after ``enable(True)`` it returns a ``jax.profiler.TraceAnnotation``, which
the profiler keeps in memory and writes into the same ``.xplane.pb`` as the
device operations, so host phases and device time share one clock.  Span
names start with ``mv4pg.``.

Counters are process-wide integers, always on (one add each).
:func:`to_host` is ``np.asarray`` that also counts the bytes and the pulls
of a device-to-host copy under ``<counter>_bytes`` and ``<counter>_pulls``.
"""
from __future__ import annotations

import contextlib
from typing import Dict

import jax
import numpy as np

_on = False
_OFF = contextlib.nullcontext()
_counts: Dict[str, int] = {}


def enable(on: bool) -> None:
    """Turn the profiler spans on or off for the whole process."""
    global _on
    _on = bool(on)


def span(name: str):
    """A context manager covering one phase: a profiler annotation while
    tracing is on, else a shared no-op."""
    return jax.profiler.TraceAnnotation(name) if _on else _OFF


def count(name: str, n: int = 1) -> None:
    _counts[name] = _counts.get(name, 0) + n


def value(name: str) -> int:
    """One counter's value (0 before its first add)."""
    return _counts.get(name, 0)


def counters() -> Dict[str, int]:
    """A snapshot of every counter."""
    return dict(_counts)


def to_host(x, counter: str) -> np.ndarray:
    """``np.asarray(x)``, counted as one pull of ``x.nbytes`` bytes."""
    a = np.asarray(x)
    count(counter + "_bytes", a.nbytes)
    count(counter + "_pulls")
    return a
