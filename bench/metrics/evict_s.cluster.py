"""Host seconds per call replaying memory evictions, in s.

The program's span ``cluster.evict`` (``repro.telemetry``): phase D of
the cluster replay, the screen of workers whose assigned images pass the
budget and each such worker's eviction fixed point. Summed over the
traced window and divided by its calls. No such span in the trace: no
value. Moves ``sweep_events_per_s``.
"""
from bench.metrics import _cluster_spans


def reduce(tr: dict, ctx: dict):
    return _cluster_spans.total(tr, ("cluster.evict",))
