"""Host seconds per call merging the fleet's events, in s.

The program's span ``cluster.merge`` (``repro.telemetry``): phase A of
the cluster replay, flattening and ranking every event in arrival order
and building the end-time frame the windows scan reads. Summed over the
traced window and divided by its calls. No such span in the trace: no
value. Moves ``sweep_events_per_s``.
"""
from bench.metrics import _cluster_spans


def reduce(tr: dict, ctx: dict):
    return _cluster_spans.total(tr, ("cluster.merge",))
