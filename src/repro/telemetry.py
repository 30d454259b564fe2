"""Host spans and counters of the program.

A span is a ``jax.profiler.TraceAnnotation``: while a profiler runs, it is
written into the profiler's own trace, on the calling thread's host line
and on the same clock as the device planes, and spans nest by time on that
line. With no profiler running it records nothing, so there is no switch:
the profiler being on is tracing on.

A counter is a process-wide integer total, counted whether or not a
profiler runs; ``counters()`` is a copy of them all. Readers take
differences or ratios, so nothing needs resetting.

The spans and counters of one ``sweep()`` call (``core.experiment`` and
the hybrid driver in ``core.simulator``): the ``sweep`` span is the call,
with leaf spans ``sweep.pad``, ``sweep.bucket``, ``sweep.gap_columns``,
``sweep.put``, ``sweep.dispatch``, ``sweep.pull``, ``sweep.scatter``,
``sweep.arima_replay`` and ``sweep.fill``; counters ``sweep.event_steps``
(configs x real events stepped) and ``sweep.app_steps`` (configs x rows x
width the scans step through, the kernel's tile padding included).

The spans and counters of one invoker-cluster replay
(``serving.cluster_vector``, the vectorized engine): leaf spans
``cluster.merge`` (phase A), ``cluster.bucket``, ``cluster.gap_columns``,
``cluster.put`` and ``cluster.pull`` (phase B, per chunk),
``cluster.replay`` (phase C), ``cluster.evict`` (phase D) and
``cluster.results``; counters ``cluster.events``, ``cluster.evictions``,
``cluster.evict_rounds``, ``cluster.evict_candidates`` and
``cluster.risky_workers``.
"""
from __future__ import annotations

from typing import Dict

import jax

_COUNTS: Dict[str, int] = {}


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A context manager timing its block as ``name`` in a running
    profiler's trace."""
    return jax.profiler.TraceAnnotation(name)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the process-wide counter ``name``."""
    _COUNTS[name] = _COUNTS.get(name, 0) + int(n)


def counters() -> Dict[str, int]:
    """A copy of every counter's total so far."""
    return dict(_COUNTS)
