"""Host seconds per call blocked on the sweep's scans, in s.

The program's ``sweep.pull`` spans (``repro.telemetry``): each band's
``np.asarray`` of a chunk's scan outputs, which waits for the scan to end
and then copies the results to the host. Summed over the traced window
and divided by its calls. No such span in the trace: no value. Moves
``sweep_events_per_s``.
"""
from bench.metrics import _spans


def reduce(tr: dict, ctx: dict):
    return _spans.total(tr, ("sweep.pull",))
