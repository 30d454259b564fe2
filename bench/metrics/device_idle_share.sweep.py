"""Device idle share of the traced sweep window, in %.

1 minus the union of the device's operation intervals over the span from
the first traced call's start to the last one's end, averaged over the
chips the cell uses. Moves ``sweep_events_per_s``: it falls when the host
stops holding the chip back (results kept on the device across chunks).
"""
from bench import trace


def reduce(tr: dict, ctx: dict):
    return trace.idle_share_pct(tr)
