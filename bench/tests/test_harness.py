"""``bench/run.py`` and the harness's lookup by name, on the CPU."""
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import harness
from bench.tests import cpu_cell

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _run_py(cwd, *args, **env):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, "bench/run.py", *args],
                          capture_output=True, text=True, timeout=300,
                          cwd=cwd, env=env)


ARGS = ("--workload", "delaunay_n20.solve_1col", "--seed", "5",
        "--seconds", "1", "--trace", "0")


def test_run_refuses_the_cpu_naming_the_platform():
    p = _run_py(ROOT, *ARGS, PYTHONPATH=str(ROOT / "src"))
    assert p.returncode != 0
    assert "'cpu'" in p.stderr
    assert p.stdout.strip() == ""


def test_run_refuses_a_directory_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = _run_py(tmp_path, *ARGS, PYTHONPATH="")
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_benchmark_json_names_a_file_for_everything():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        assert (ROOT / c["file"]).is_file()
    for w in spec["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        cell = harness.load_cell(ROOT, w["name"])
        assert (ROOT / "bench" / "kinds"
                / f"{cell.traffic['kind']}.py").is_file()
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in spec["per_layer"]:
        assert m["workloads"] and set(m["workloads"]) <= {
            w["name"] for w in spec["workloads"]}
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"])
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_a_new_config_mix_and_metric_are_found_by_name(tmp_path,
                                                       monkeypatch):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    before = _digests(tmp_path / "bench")
    b = tmp_path / "bench"
    conf = json.loads((b / "configs" / "delaunay_n20.json").read_text())
    conf["generator_params"] = {"seed": 3, "log2n": 9, "shift": 0.5}
    conf["n"] = 2 ** 9
    (b / "configs" / "delaunay_n9.json").write_text(json.dumps(conf))
    (b / "traffic" / "solve_pairs.json").write_text(json.dumps({
        "kind": "solve", "widths": {"2": 1.0}, "buckets": [2], "pool": 4,
        "check_sample": 4}))
    (b / "metrics" / "answered.py").write_text(
        "def read(run):\n    return len(run.requests)\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "delaunay_n9", "source": "test",
                            "file": "bench/configs/delaunay_n9.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "delaunay_n9.solve_pairs",
                              "config": "delaunay_n9",
                              "traffic": "solve_pairs", "chips": 1,
                              "why": "test"})
    spec["end_to_end"].append({"name": "answered", "unit": "requests",
                               "better": "higher", "bound": 0.25,
                               "source": "host_clock",
                               "workloads": ["delaunay_n9.solve_pairs"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    r = cpu_cell.run("delaunay_n9.solve_pairs", root=tmp_path,
                     overrides={}, patch=monkeypatch.setattr)
    assert r["correct"], r["checks"]
    assert r["metrics"]["answered"]["value"] == r["attempted"] >= 1
    after = _digests(tmp_path / "bench")
    assert {k: after[k] for k in before} == before


def test_inputs_of_another_size_than_the_configuration_states_are_refused(
        monkeypatch):
    overrides = {"config": {"generator_params": {"log2n": 10},
                            "n": 2 ** 11}}
    with pytest.raises(ValueError, match="states n = 2048"):
        cpu_cell.run("delaunay_n20.solve_1col", overrides=overrides,
                     patch=monkeypatch.setattr)
