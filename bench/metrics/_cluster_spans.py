"""Program spans of the traced window, for the readers of the
``cluster.*`` spans (``repro.telemetry``, ``serving.cluster_vector``):
each span's seconds per timed call.

Only the calling thread's host events named ``cluster.*`` that start
inside the window count, and the sum is divided by the number of timed
calls (``bench.call`` events). A trace without any ``cluster.*`` span, as
from a program that records none, reads as nothing, not as 0.
"""
from typing import Dict, Optional, Sequence

from bench import trace

PREFIX = "cluster."


def per_call(tr: dict) -> Optional[Dict[str, float]]:
    """Seconds per timed call of each ``cluster.*`` span name, or None."""
    span = trace.window(tr)
    calls = sum(1 for e in tr["host"] if e[0] == trace.CALL)
    if span is None or not calls:
        return None
    out: Dict[str, float] = {}
    for name, s, d in tr["host"]:
        if name.startswith(PREFIX) and span[0] <= s <= span[1]:
            out[name] = out.get(name, 0.0) + d / 1e9 / calls
    return out or None


def total(tr: dict, names: Sequence[str]) -> Optional[float]:
    """Seconds per timed call of the spans ``names`` together; None where
    the trace holds none of them."""
    got = per_call(tr) or {}
    found = [got[n] for n in names if n in got]
    return sum(found) if found else None


def coverage(tr: dict) -> Optional[float]:
    """The least share, over the timed calls, of a call's time that its
    ``cluster.*`` spans (leaves of one thread, so they do not overlap)
    cover; None without a call or a span."""
    calls = [(s, s + d) for name, s, d in tr["host"] if name == trace.CALL]
    spans = [(s, d) for name, s, d in tr["host"] if name.startswith(PREFIX)]
    if not calls or not spans:
        return None
    return min((sum(d for s, d in spans if lo <= s <= hi) / (hi - lo)
                for lo, hi in calls if hi > lo), default=None)
