"""Share of the cluster windows scan's HBM roofline, in %.

Time: the device seconds of the policy-window scan programs
(``serving.cluster_vector._hybrid_windows_scan`` and its app-sharded
twin, named below), summed over chips. Least time: the bytes any
implementation must move (``bench.cluster_cost.windows_least_bytes``:
each real event read once, two float32 window bounds written per event,
one flag per active app) over the chips' HBM bandwidth (``bench.peaks``).
No scan program in the trace: no value. Moves ``sweep_events_per_s``.
"""
from bench import trace

PROGRAMS = ("_hybrid_windows_scan", "_hybrid_windows_scan_sharded")


def reduce(tr: dict, ctx: dict):
    seconds = trace.program_seconds(tr, PROGRAMS)
    if not seconds or ctx.get("peaks") is None:
        return None
    least = ctx["least_bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
    ctx["log"]("windows_scan", scan_s=seconds,
               least_bytes=ctx["least_bytes"], least_s=least)
    return 100.0 * least / seconds
