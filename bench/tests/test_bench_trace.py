"""The reduction from a profiler trace to the per-layer metrics."""
import json
import os

import pytest

from bench import harness, trace

MS = 1_000_000


def _metric(name):
    return harness.load_module(
        os.path.join(harness.ROOT, "bench", "metrics", name + ".py"),
        "m_" + name.replace(".", "_"))


def _synthetic():
    dev = "/device:TPU:0"
    return {"devices": {dev: {
        trace.OPS_LINE: [["fusion.1", 10 * MS, 20 * MS],
                         ["fusion.2", 25 * MS, 10 * MS],      # overlaps
                         ["kernel", 60 * MS, 20 * MS],
                         ["late", 150 * MS, 10 * MS]],        # outside
        trace.MODULES_LINE: [["jit__hybrid_sweep_scan_pallas(7)", 10 * MS,
                              25 * MS],
                             ["jit_other", 60 * MS, 20 * MS]]}},
        "host": [[trace.CALL, 0, 50 * MS], [trace.CALL, 50 * MS, 50 * MS],
                 ["PjitFunction(x)", 40 * MS, 15 * MS]]}


def test_window_and_busy_union():
    tr = _synthetic()
    assert trace.window(tr) == (0, 100 * MS)
    assert trace.busy_seconds(tr) == [pytest.approx(0.045)]
    assert trace.idle_share_pct(tr) == pytest.approx(55.0)


def test_program_seconds_match_by_name_and_are_null_without_a_match():
    tr = _synthetic()
    assert trace.program_seconds(tr, ["_hybrid_sweep_scan_pallas"]) == \
        pytest.approx(0.025)
    assert trace.program_seconds(tr, ["_hybrid_sweep_scan"]) is None
    assert trace.program_seconds(tr, ["absent"]) is None


@pytest.mark.parametrize("module", ["jit__hybrid_sweep_scan_pallas",
                                    "jit__hybrid_sweep_scan_pallas.3",
                                    "jit__hybrid_sweep_scan_pallas(12)"])
def test_program_seconds_take_the_module_names_suffixes(module):
    tr = _synthetic()
    tr["devices"]["/device:TPU:0"][trace.MODULES_LINE] = [[module, 0, MS]]
    assert trace.program_seconds(tr, ["_hybrid_sweep_scan_pallas"]) == \
        pytest.approx(0.001)
    tr["devices"]["/device:TPU:0"][trace.MODULES_LINE] = [
        [module.replace("pallas", "pallas_sharded"), 0, MS]]
    assert trace.program_seconds(tr, ["_hybrid_sweep_scan_pallas"]) is None


def test_roofline_is_null_not_zero_without_its_programs():
    tr = _synthetic()
    tr["devices"]["/device:TPU:0"][trace.MODULES_LINE] = [
        ["jit_other", 0, MS]]
    ctx = dict(least_bytes=10 ** 6, search_ops=1, log=lambda *a, **k: None,
               peaks={"hbm_bytes_per_s": 819e9})
    assert _metric("sweep_scan_roofline.sweep").reduce(tr, ctx) is None


def test_idle_share_is_null_without_a_device():
    tr = {"devices": {}, "host": [[trace.CALL, 0, MS]]}
    assert _metric("device_idle_share.sweep").reduce(tr, {}) is None


def test_breakdown_names_gaps_by_the_innermost_host_event():
    out = trace.breakdown(_synthetic())
    assert out["device_ops"][0][0] == "fusion.1"
    gaps = dict(out["idle_gaps"])
    assert gaps["PjitFunction(x)"] == pytest.approx(0.025)   # 35..60 ms
    assert len(out["idle_gaps"]) <= trace.TOP


def test_a_recorded_profile_finds_the_calling_thread():
    # the host line is named after the thread (``python3``, ...): it is
    # found by the CALL annotations it holds, so the window exists
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x * 2.0).sum())
    f(jnp.ones(8)).block_until_ready()
    cap = trace.Capture()
    for _ in range(2):
        with jax.profiler.TraceAnnotation(trace.CALL):
            f(jnp.ones(8)).block_until_ready()
    tr = cap.stop()
    span = trace.window(tr)
    assert span is not None and span[1] > span[0]
    assert sum(e[0] == trace.CALL for e in tr["host"]) == 2


class _Profile:
    """A profile's planes, lines and events, as ``ProfileData`` has them."""

    def __init__(self, planes):
        self.planes = [self._ns(name=p, lines=[self._ns(name=ln, events=[
            self._ns(name=n, start_ns=s, duration_ns=d) for n, s, d in ev])
            for ln, ev in lines.items()]) for p, lines in planes.items()]

    @staticmethod
    def _ns(**kw):
        import types
        return types.SimpleNamespace(**kw)


@pytest.mark.parametrize("thread", ["python", "python3", "python3.12"])
def test_normalize_keeps_the_line_with_the_calls_whatever_its_name(thread):
    prof = _Profile({
        "/host:CPU": {thread: [[trace.CALL, 0, 10], ["PjitFunction(f)", 1, 5]],
                      "tf_pool/1": [["ThreadpoolListener::Record", 2, 1]]},
        "/device:TPU:0": {trace.OPS_LINE: [["fusion", 3, 4]]},
        "/host:metadata": {}})
    tr = trace.normalize(prof)
    assert [e[0] for e in tr["host"]] == [trace.CALL, "PjitFunction(f)"]
    assert trace.busy_seconds(tr) == [pytest.approx(4e-9)]


RECORDED = os.path.join(harness.ROOT, "bench", "tests", "data",
                        "trace_grid32_v5e.json")


def test_the_reduction_of_a_recorded_chip_trace():
    """20 ms of a traced grid32 run on one TPU v5e (``--save-trace``):
    the scan program starts 2 ms into the slice and runs past its end."""
    with open(RECORDED) as f:
        tr = json.load(f)
    assert list(tr["devices"]) == ["/device:TPU:0"]
    lo, hi = trace.window(tr)
    assert hi - lo == 20 * MS
    busy = trace.busy_seconds(tr)
    assert len(busy) == 1 and 0.017 < busy[0] <= 0.018
    assert trace.idle_share_pct(tr) == pytest.approx(10.0, abs=0.1)
    scan = trace.program_seconds(tr, ["_hybrid_sweep_scan_pallas"])
    assert scan == pytest.approx(0.018)
    ctx = dict(least_bytes=10 ** 6, search_ops=1, log=lambda *a, **k: None,
               peaks={"hbm_bytes_per_s": 819e9})
    share = _metric("sweep_scan_roofline.sweep").reduce(tr, ctx)
    assert share == pytest.approx(100.0 * 10 ** 6 / 819e9 / 0.018)
    out = trace.breakdown(tr)
    assert out["device_ops"][0][0].startswith("%while = (s32[], s32[32,60,")
    assert all("{" not in n and len(n) <= trace.LABEL
               for n, _ in out["device_ops"])


def test_op_label_drops_layouts_and_cuts_the_name():
    name = ("%copy.8 = f32[2859,20160]{1,0:T(8,128)} copy(f32[2859,20160]"
            "{1,0:T(8,128)} %gaps_0_.1), frontend_attributes={a={b}}")
    assert trace.op_label(name) == ("%copy.8 = f32[2859,20160] copy("
                                    "f32[2859,20160] %gaps_0_.1), "
                                    "frontend_attributes=")
    assert len(trace.op_label("x" * 500)) == trace.LABEL


def test_excerpt_keeps_a_slice_around_the_longest_program():
    ex = trace.excerpt(_synthetic(), span_ms=30.0)
    assert trace.window(ex) == (8 * MS, 38 * MS)
    ops = ex["devices"]["/device:TPU:0"][trace.OPS_LINE]
    assert [o[0] for o in ops] == ["fusion.1", "fusion.2"]
    assert [h[0] for h in ex["host"]] == [trace.CALL]
