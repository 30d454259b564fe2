"""Least bytes of the cluster replay's policy-window scan, from shapes.

The count depends only on the fleet (its real invocation events and the
apps that have any), never on how the scan lays out its state: any
implementation reads each event once and writes the two window bounds
decided after it, and one forecast flag per app.
"""
from __future__ import annotations

EVENT_BYTES = 4          # each real event read once, as one 4-byte word
BOUND_BYTES = 2 * 4      # (load, unload) written per event, float32 each
FLAG_BYTES = 1           # one out-of-bounds-heavy flag per active app


def windows_least_bytes(n_events: int, n_active_apps: int) -> int:
    """Bytes any implementation of one windows scan must move through
    HBM."""
    return n_events * (EVENT_BYTES + BOUND_BYTES) + n_active_apps * FLAG_BYTES
