"""Golden-trace regression tests: every engine vs checked-in oracle results.

The fixtures under ``tests/golden/`` pin per-app cold counts, final policy
windows, and wasted minutes of the float64 scalar oracle on deterministic
seeded traces. Any edit to the hybrid decision math (now single-sourced in
``repro.core.policy_math``) that shifts a verdict fails here loudly;
deliberate formula changes re-record via ``scripts/regen_golden.py``.
"""
import json
import os

import numpy as np
import pytest

from repro.core.experiment import EngineOptions, HybridSpec, run

from golden_traces import GOLDEN_TRACES

GOLDEN_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "golden")

ENGINES = {
    "scalar": lambda t, cfg: run(t, HybridSpec.from_config(cfg),
                                 engine="scalar"),
    "jnp_f64": lambda t, cfg: run(t, HybridSpec.from_config(cfg),
                                  engine="fused"),
    "pallas_f32": lambda t, cfg: run(t, HybridSpec.from_config(cfg),
                                     engine="pallas"),
    "pallas_f32_chunked": lambda t, cfg: run(
        t, HybridSpec.from_config(cfg), engine="pallas",
        options=EngineOptions(app_chunk=7)),
}


@pytest.fixture(scope="module", params=sorted(GOLDEN_TRACES))
def golden_case(request):
    name = request.param
    path = os.path.join(GOLDEN_DIR, f"{name}.json")
    with open(path) as f:
        want = json.load(f)
    make_trace, cfg = GOLDEN_TRACES[name]
    return name, make_trace(), cfg, want


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_golden_trace(golden_case, engine):
    name, trace, cfg, want = golden_case
    assert trace.n_apps == want["n_apps"]
    res = ENGINES[engine](trace, cfg)
    err = f"{engine} vs golden {name} (see scripts/regen_golden.py)"
    np.testing.assert_array_equal(res.invocations,
                                  np.asarray(want["invocations"]),
                                  err_msg=err)
    np.testing.assert_array_equal(res.cold, np.asarray(want["cold"]),
                                  err_msg=err)
    np.testing.assert_array_equal(res.final_prewarm,
                                  np.asarray(want["final_prewarm"]),
                                  err_msg=err)
    np.testing.assert_array_equal(res.final_keep_alive,
                                  np.asarray(want["final_keep_alive"]),
                                  err_msg=err)
    # float64 engines reproduce the recorded waste exactly (JSON round-trips
    # float64); the float32 engine accumulates its gap terms in float32
    tol = dict(rtol=0, atol=0) if engine in ("scalar", "jnp_f64") \
        else dict(rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(res.wasted_minutes,
                               np.asarray(want["wasted_minutes"]),
                               err_msg=err, **tol)
