"""Host seconds per call putting the sweep's results in place, in s.

The program's spans (``repro.telemetry``) ``sweep.scatter`` (each chunk's
results into the [configs, apps] arrays, trailing waste included),
``sweep.arima_replay`` (the forecast post-pass) and ``sweep.fill`` (the
family's rows into the sweep's result), summed over the traced window and
divided by its calls. No such span in the trace: no value. Moves
``sweep_events_per_s``.
"""
from bench.metrics import _spans

SPANS = ("sweep.scatter", "sweep.arima_replay", "sweep.fill")


def reduce(tr: dict, ctx: dict):
    return _spans.total(tr, SPANS)
