"""Profiler capture and the reductions every per-layer metric shares.

A traced run records the window with ``jax.profiler`` into a temporary
directory, reads the ``.xplane.pb`` back with ``jax.profiler.ProfileData``
and keeps a small normalized form, which is all the metric readers see:

  {"devices": {plane: {line: [[name, start_ns, dur_ns], ...]}},
   "host": [[name, start_ns, dur_ns], ...]}     # the calling thread

Device planes are the ones named ``/device:TPU:<n>``; on each, ``XLA Ops``
holds the operations that ran and ``XLA Modules`` the compiled programs.
The benchmark wraps each timed call in a ``TraceAnnotation`` named
``CALL``, which bounds the traced window.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

CALL = "bench.call"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
TOP = 10
LAYOUT, LABEL = re.compile(r"\{[^{}]*\}"), 160


class Capture:
    """Profiles a stretch of the run; ``stop()`` returns its normalized
    trace and deletes the files."""

    def __init__(self):
        import jax
        self._jax = jax
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> dict:
        try:
            self._jax.profiler.stop_trace()
            paths = glob.glob(os.path.join(self.dir, "plugins", "profile",
                                           "*", "*.xplane.pb"))
            if not paths:
                raise RuntimeError("the profiler wrote no .xplane.pb")
            return normalize(self._jax.profiler.ProfileData.from_file(
                paths[0]))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def normalize(profile) -> dict:
    """The device planes' lines and the events of the host thread that
    made the timed calls (the line holding the ``CALL`` annotations; a
    thread's line is named after it, e.g. ``python3``, so it is found by
    what it holds, not by its name)."""
    out = {"devices": {}, "host": []}
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            out["devices"][plane.name] = {
                line.name: [[e.name, e.start_ns, e.duration_ns]
                            for e in line.events]
                for line in plane.lines}
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                events = [[e.name, e.start_ns, e.duration_ns]
                          for e in line.events]
                if any(e[0] == CALL for e in events):
                    out["host"] += events
    return out


def summary(tr: dict) -> str:
    """One line naming the planes and lines a trace holds, with counts."""
    parts = [f"{p}:" + ",".join(f"{ln}={len(ev)}" for ln, ev in lines.items())
             for p, lines in sorted(tr["devices"].items())]
    return f"trace host_events={len(tr['host'])} " + " ".join(parts)


def window(tr: dict) -> Optional[Tuple[float, float]]:
    """(start, end) in ns of the traced calls, or None without any."""
    calls = [(s, s + d) for name, s, d in tr["host"] if name == CALL]
    if not calls:
        return None
    return min(s for s, _ in calls), max(e for _, e in calls)


def _union(intervals: Iterable[Tuple[float, float]], lo: float,
           hi: float) -> List[Tuple[float, float]]:
    """Merged intervals clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def device_ops(tr: dict) -> Dict[str, list]:
    """Each device plane's operations (the ``XLA Ops`` line)."""
    return {p: lines.get(OPS_LINE, []) for p, lines in tr["devices"].items()}


def busy_seconds(tr: dict) -> Optional[List[float]]:
    """Seconds of the traced window in which an operation ran, per device;
    None when the trace has no window, or no device plane holds an
    operation (a layout this reader does not know reads as nothing, not
    as an idle chip)."""
    span = window(tr)
    ops = device_ops(tr)
    if span is None or not any(ops.values()):
        return None
    return [sum(e - s for s, e in _union(((o[1], o[1] + o[2]) for o in ev),
                                         *span)) / 1e9
            for _, ev in sorted(ops.items())]


def idle_share_pct(tr: dict) -> Optional[float]:
    """Mean over devices of the window's share with no operation running
    (1 minus the union of operation intervals over the window), in %."""
    busy = busy_seconds(tr)
    span = window(tr)
    if not busy or span is None or span[1] <= span[0]:
        return None
    width = (span[1] - span[0]) / 1e9
    return 100.0 * (1.0 - sum(busy) / len(busy) / width)


def program_seconds(tr: dict, names: Sequence[str]) -> Optional[float]:
    """Device seconds of the compiled programs named ``names`` (jitted
    function names; ``XLA Modules`` events are ``jit_<name>`` with an
    optional ``.<n>`` or ``(<id>)``) inside the traced window, summed over
    devices;
    None when none ran."""
    pat = re.compile(r"^jit_(%s)([.(].*)?$" % "|".join(map(re.escape,
                                                          names)))
    lo, hi = window(tr) or (float("-inf"), float("inf"))
    total, hits = 0.0, 0
    for lines in tr["devices"].values():
        for name, s, d in lines.get(MODULES_LINE, []):
            if pat.match(name) and s < hi and s + d > lo:
                total += min(s + d, hi) - max(s, lo)
                hits += 1
    return total / 1e9 if hits else None


def op_label(name: str) -> str:
    """A device op's name as the breakdown gives it: the TPU trace names
    an op by its whole HLO text, which is kept without its layouts
    (``{...}``) and cut to ``LABEL`` characters."""
    prev = None
    while prev != name:
        prev, name = name, LAYOUT.sub("", name)
    return name[:LABEL]


def breakdown(tr: dict) -> dict:
    """The device operations that took most time (summed over devices)
    and the longest idle gaps, each named by the innermost host event on
    the main thread at the gap's middle."""
    span = window(tr)
    ops = device_ops(tr)
    by_op: Dict[str, float] = {}
    for ev in ops.values():
        for name, s, d in ev:
            if span is None or (s < span[1] and s + d > span[0]):
                key = op_label(name)
                by_op[key] = by_op.get(key, 0.0) + d / 1e9
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
    gaps: List[Tuple[float, float]] = []
    if span is not None:
        for ev in ops.values():
            busy = _union(((s, s + d) for _, s, d in ev), *span)
            edges = [span[0]] + [x for iv in busy for x in iv] + [span[1]]
            gaps += [(edges[i], edges[i + 1])
                     for i in range(0, len(edges), 2)
                     if edges[i + 1] > edges[i]]
    host = sorted(tr["host"], key=lambda e: e[2])    # innermost first
    named: Dict[str, float] = {}
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]:
        mid = 0.5 * (s + e)
        name = next((h[0] for h in host if h[1] <= mid <= h[1] + h[2]),
                    "outside any host event")
        key = name
        k = 2
        while key in named:
            key = f"{name} #{k}"
            k += 1
        named[key] = (e - s) / 1e9
    return {"device_ops": [[n, v] for n, v in top_ops],
            "idle_gaps": [[n, v] for n, v in named.items()]}


def excerpt(tr: dict, span_ms: float = 20.0) -> dict:
    """A small slice of ``tr`` to keep as a test's input: ``span_ms`` from
    just before the longest compiled program starts, with every event
    that starts inside it, and one timed call spanning the slice."""
    mods = [e for lines in tr["devices"].values()
            for e in lines.get(MODULES_LINE, [])]
    lo = (max(mods, key=lambda e: e[2])[1] if mods
          else window(tr)[0]) - 2_000_000
    hi = lo + int(span_ms * 1e6)
    keep = lambda ev: [e for e in ev if lo <= e[1] < hi]
    return {"devices": {p: {ln: keep(ev) for ln, ev in lines.items()}
                        for p, lines in tr["devices"].items()},
            "host": [[CALL, lo, hi - lo]]
            + [e for e in keep(tr["host"]) if e[0] != CALL]}
