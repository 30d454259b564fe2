"""Work and least bytes of the policy sweep, from fleet and grid shapes.

The counts depend only on the fleet (its real invocation events and the
apps that have any) and on the number of configurations, never on how a
kernel lays out its state, so a kernel that keeps its histograms in fast
memory is held to the same count as one that streams them through HBM.
"""
from __future__ import annotations

EVENT_BYTES = 4          # each real event read once, as one 4-byte word
RESULT_FIELDS = 5        # cold, waste, OOB flag, prewarm, unload per row
RESULT_BYTES = 4


def sweep_least_bytes(n_events: int, n_active_apps: int,
                      n_configs: int) -> int:
    """Bytes any implementation of one sweep must move through HBM: every
    real event read once, and every (config, app) result written once."""
    return (n_events * EVENT_BYTES
            + n_configs * n_active_apps * RESULT_FIELDS * RESULT_BYTES)


def sweep_search_ops(n_events: int, n_configs: int, n_bins: int) -> int:
    """Compares of one sweep's percentile searches: per event and config,
    two binary searches over the bins. A count, printed beside the bytes;
    no published vector-unit peak exists to hold it against."""
    return n_events * n_configs * 2 * max(n_bins, 1).bit_length()
