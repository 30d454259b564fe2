"""Device idle share of the traced cluster-replay window, in %.

1 minus the union of the device's operation intervals over the span from
the first traced call's start to the last one's end, averaged over the
chips the cell uses. Moves ``sweep_events_per_s``: only phase B's windows
scan runs on the chip, so it falls as the host phases (merge, replay,
eviction) shrink or overlap the scan.
"""
from bench import trace


def reduce(tr: dict, ctx: dict):
    return trace.idle_share_pct(tr)
