"""The harness finds everything by name, holds BENCHMARK.json to its
contract, and refuses to run without the chips a cell asks for."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import harness

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return harness.load_json(ROOT, "BENCHMARK.json")


def test_benchmark_json_keeps_to_its_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert bench["command"] == ["python3", "bench/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(map(NAME.match, c["reduced"]))
        assert c["file"].startswith("bench/") and os.path.isfile(
            os.path.join(ROOT, c["file"]))
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
    assert len({c["file"] for c in bench["configs"]}) == len(configs)
    cells = bench["workloads"]
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
    used = {w["config"] for w in cells}
    assert used == set(configs)
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(names)) == len(names)
    assert "setup_s" in names
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= {w["name"] for w in cells}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    assert len(json.dumps(bench)) < 64 * 1024


def test_every_cell_resolves_from_files(bench):
    for w in bench["workloads"]:
        r = harness.resolve(bench, w["name"])
        assert r["config"]["name"] == w["config"]
        assert hasattr(r["driver"], "Work")
        assert r["per_layer"] and all(hasattr(r["readers"][m["name"]],
                                              "reduce")
                                      for m in r["per_layer"])
        reported = {m["name"] for m in r["end_to_end"]}
        assert "setup_s" in reported and len(reported) >= 2


def test_configs_mixes_and_metrics_are_found_by_name(tmp_path):
    """A new cell is files plus entries: nothing in the harness names it."""
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    for sub in ("traffic", "drivers", "metrics"):
        (tmp_path / "bench" / sub).mkdir()
    (tmp_path / "bench" / "configs" / "toy.json").write_text(
        json.dumps({"name": "toy", "fleet": {}}))
    (tmp_path / "bench" / "traffic" / "toy-mix.json").write_text(
        json.dumps({"driver": "toy_driver"}))
    (tmp_path / "bench" / "drivers" / "toy_driver.py").write_text(
        "class Work:\n    KIND = 'toy'\n")
    (tmp_path / "bench" / "metrics" / "toy_share.x.py").write_text(
        "def reduce(tr, ctx):\n    return 42.0\n")
    (tmp_path / "bench" / "metrics" / "other.py").write_text(
        "def reduce(tr, ctx):\n    return 1.0\n")
    bench = {
        "configs": [{"name": "toy", "file": "bench/configs/toy.json"}],
        "workloads": [{"name": "toy-cell", "config": "toy",
                       "traffic": "toy-mix", "chips": 1}],
        "end_to_end": [{"name": "toy_rate", "unit": "1/s"},
                       {"name": "setup_s", "unit": "s"},
                       {"name": "elsewhere", "unit": "s",
                        "workloads": ["other-cell"]}],
        "per_layer": [{"name": "toy_share.x", "unit": "%",
                       "moves": "toy_rate"},
                      {"name": "other", "unit": "%", "moves": "elsewhere",
                       "workloads": ["other-cell"]}],
    }
    r = harness.resolve(bench, "toy-cell", root=str(tmp_path))
    assert r["config"] == {"name": "toy", "fleet": {}}
    assert r["traffic"]["driver"] == "toy_driver"
    assert r["driver"].Work.KIND == "toy"
    assert [m["name"] for m in r["end_to_end"]] == ["toy_rate", "setup_s"]
    assert list(r["readers"]) == ["toy_share.x"]
    assert r["readers"]["toy_share.x"].reduce({}, {}) == 42.0
    with pytest.raises(KeyError, match="no workload"):
        harness.resolve(bench, "missing", root=str(tmp_path))


def _run(cwd, *args):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "JAX_PLATFORMS")}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def test_run_without_a_tpu_exits_nonzero_before_any_work():
    p = _run(ROOT, "--workload", "grid32-azure10k-14d", "--seed",
             str(2 ** 31 + 9), "--seconds", "1", "--trace", "0")
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "needs 1 TPU chip" in p.stderr
    assert "fleet" not in p.stdout      # no fleet was made


def test_run_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path), "--workload", "grid32-azure10k-14d", "--seed",
             "1", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
