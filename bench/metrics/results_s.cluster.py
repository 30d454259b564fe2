"""Host seconds per call assembling the cluster replay's result, in s.

The program's span ``cluster.results`` (``repro.telemetry``): loads and
unloads, resident time, latencies in arrival order and per-worker
counters. Summed over the traced window and divided by its calls. No
such span in the trace: no value. Moves ``sweep_events_per_s``.
"""
from bench.metrics import _cluster_spans


def reduce(tr: dict, ctx: dict):
    return _cluster_spans.total(tr, ("cluster.results",))
