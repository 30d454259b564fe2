"""Host seconds per call preparing the sweep's chunks, in s.

The program's spans (``repro.telemetry``) ``sweep.pad`` (the padded
trace), ``sweep.bucket`` (each bucket's copy of its apps' events),
``sweep.gap_columns`` (the float64 gap classification the Pallas kernel
reads) and ``sweep.put`` (the host-to-device enqueue), summed over the
traced window and divided by its calls. The device waits through all of
it unless a scan was dispatched before. Logs every ``sweep.*`` span's
seconds per call and the least share of a call the spans cover. No such
span in the trace: no value. Moves ``sweep_events_per_s``.
"""
from bench.metrics import _spans

SPANS = ("sweep.pad", "sweep.bucket", "sweep.gap_columns", "sweep.put")


def reduce(tr: dict, ctx: dict):
    parts = _spans.per_call(tr)
    if parts:
        ctx["log"]("spans", covered=_spans.coverage(tr), **parts)
    return _spans.total(tr, SPANS)
