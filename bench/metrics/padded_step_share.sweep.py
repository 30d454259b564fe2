"""Share of the sweep scans' steps spent on padding, in %.

From the program's counters (``repro.telemetry``): ``sweep.event_steps``
(configs x the real events each scan steps through) over
``sweep.app_steps`` (configs x the rows each scan steps through, the
Pallas kernel's tile padding included, x the chunk's width), as
100 x (1 - event_steps / app_steps). A ratio, so the warm-up call, whose
shapes are the window's, does not move it. Both counters are logged. A
program without the counters: no value. Moves ``sweep_events_per_s``.
"""


def reduce(tr: dict, ctx: dict):
    try:
        from repro import telemetry
    except ImportError:
        return None
    counts = telemetry.counters()
    app = counts.get("sweep.app_steps")
    if not app:
        return None
    event = counts.get("sweep.event_steps", 0)
    ctx["log"]("steps", **{"sweep.event_steps": event, "sweep.app_steps": app})
    return 100.0 * (1.0 - event / app)
