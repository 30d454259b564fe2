"""Host seconds per call replaying every gap in closed form, in s.

The program's span ``cluster.replay`` (``repro.telemetry``): phase C of
the cluster replay (placement, each worker's ticks, keep-alive expiries,
pre-warm fires and cold verdicts). Summed over the traced window and
divided by its calls. No such span in the trace: no value. Moves
``sweep_events_per_s``.
"""
from bench.metrics import _cluster_spans


def reduce(tr: dict, ctx: dict):
    return _cluster_spans.total(tr, ("cluster.replay",))
