"""Host seconds per call deciding the policy windows, in s.

The program's phase-B spans (``repro.telemetry``), per chunk:
``cluster.bucket`` (the chunk's row copy), ``cluster.gap_columns`` (the
float64 gap classification the scan reads), ``cluster.put`` (the
host-to-device put and the scan's dispatch) and ``cluster.pull``
(waiting for the scan, the copy back and the scatter), summed over the
traced window and divided by its calls. Logs every ``cluster.*`` span's
seconds per call and the least share of a call the spans cover. No such
span in the trace: no value. Moves ``sweep_events_per_s``.
"""
from bench.metrics import _cluster_spans

SPANS = ("cluster.bucket", "cluster.gap_columns", "cluster.put",
         "cluster.pull")


def reduce(tr: dict, ctx: dict):
    parts = _cluster_spans.per_call(tr)
    if parts:
        ctx["log"]("cluster_spans", covered=_cluster_spans.coverage(tr),
                   **parts)
    return _cluster_spans.total(tr, SPANS)
