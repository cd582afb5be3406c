"""The four-chip cell at a tiny size on four CPU devices (a subprocess,
since the device count is fixed when JAX starts): a sound run, the
control, and each fault the cell can have, one after another."""
import json
import os
import subprocess
import sys

import pytest

from bench.tests.cpu_cell import ROOT

X4 = "delaunay_n20_x4.solve_mixed"


@pytest.fixture(scope="module")
def results() -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=f"{ROOT}{os.pathsep}{ROOT / 'src'}")
    p = subprocess.run([sys.executable, "-m", "bench.tests.cpu_cell", X4,
                        "--devices", "4"], capture_output=True, text=True,
                       timeout=600, cwd=ROOT, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_run_on_four_devices(results):
    r = results["sound"]
    assert r["correct"], r["checks"]
    assert r["device"]["count"] == 4
    assert set(r["metrics"]) == {"cols_per_s", "solve_p95_s", "setup_s"}
    assert r["diagnostics"]["compiles_in_window"] == 0


@pytest.mark.parametrize("name", ["control", "unchanged", "half_batch",
                                  "no_exchange", "altered"])
def test_control_and_faults_are_not_correct(results, name):
    r = results[name]
    assert not r["correct"], r["checks"]
    assert r["checks"]["residual_max"]["value"] > 1e3 * results["sound"][
        "checks"]["residual_max"]["value"]
