"""Share of the sweep scan's HBM roofline, in %.

Time: the device seconds of the scan programs (the Pallas sweep kernel's
``lax.scan`` and its app-sharded twin, named below), summed over chips.
Least time: the bytes any implementation must move
(``bench.cost.sweep_least_bytes``: each real event read once, each
(config, app) result written once, 4 bytes each) over the chips' HBM
bandwidth (``bench.peaks``). The ops side is not bounded: the v5e's
vector-unit rate is not published, so the search compares per byte are
printed beside it and held to no peak. No scan program in the trace: no
value. Moves ``sweep_events_per_s``.
"""
from bench import trace

PROGRAMS = ("_hybrid_sweep_scan_pallas", "_hybrid_sweep_scan_pallas_sharded")


def reduce(tr: dict, ctx: dict):
    seconds = trace.program_seconds(tr, PROGRAMS)
    if not seconds or ctx.get("peaks") is None:
        return None
    least = ctx["least_bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
    ctx["log"]("scan", scan_s=seconds, least_bytes=ctx["least_bytes"],
               least_s=least, ops_per_byte=ctx["search_ops"]
               / ctx["least_bytes"])
    return 100.0 * least / seconds
