"""Share of replayed events that evicted an image, in %.

From the program's counters (``repro.telemetry``): 100 x
``cluster.evictions`` / ``cluster.events``, totals over every replay the
process made (the warm-up's too: a ratio, and the warm-up replays the
same fleet). ``cluster.evict_rounds`` and ``cluster.risky_workers`` are
logged beside it. A program without the counters: no value. Moves
``sweep_events_per_s``: phase D's work grows with the evictions.
"""


def reduce(tr: dict, ctx: dict):
    try:
        from repro import telemetry
    except ImportError:
        return None
    counts = telemetry.counters()
    events = counts.get("cluster.events")
    if not events:
        return None
    ctx["log"]("evictions", **{k: v for k, v in counts.items()
                               if k.startswith("cluster.")})
    return 100.0 * counts.get("cluster.evictions", 0) / events
