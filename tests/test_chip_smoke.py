"""``chip_smoke.py`` on the CPU: its phases at tiny size, its refusal to
run without a TPU, and the compile-cache placement its entry points use.

The phases are the functions ``main`` calls on the chip; here they run at
a few thousand vertices (the Pallas kernel interpreted), and the
four-chip path runs on four forced host devices in a subprocess.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402


def _env(**kw) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO / "src"))
    env.update(kw)
    return env


def _run(args, cwd, **env) -> subprocess.CompletedProcess:
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, timeout=300, cwd=cwd, env=_env(**env))


@pytest.fixture(scope="module")
def tiny():
    return chip_smoke.deployment(log2n=10, seed=0)


def test_partition_phase_meets_targets(tiny):
    g, _ = tiny
    topo = chip_smoke.heterogeneous_topology(g.n)
    part = chip_smoke.partition_phase(g, topo)
    assert part.shape == (g.n,) and set(part.tolist()) == set(range(6))


def test_serve_phase_repeat_is_warm_hit(tiny):
    _, csr = tiny
    svc = chip_smoke.serve_phase(csr, widths=(1, 3, 8, 3))
    assert svc.stats.operator_misses == 1
    assert svc.stats.bucket_hits == 1


def test_kernel_phase_matches_coo():
    chip_smoke.kernel_phase(shape=(16, 16), want_kernel=False)


def test_checks_fail_loudly(tiny):
    g, _ = tiny
    with pytest.raises(chip_smoke.SmokeFailure, match="exceeds its target"):
        chip_smoke.partition_phase(g, chip_smoke.heterogeneous_topology(g.n),
                                   eps=-0.5)


FOUR_CHIP = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, {repo!r})
    import jax
    import chip_smoke
    g, csr = chip_smoke.deployment(log2n=10, seed=0)
    chip_smoke.four_chip_phase(g, csr, jax.devices()[:4])
    print("FOUR_CHIP_OK")
""")


def test_four_chip_phase_on_host_devices():
    proc = _run(["-c", FOUR_CHIP.format(repo=str(REPO))], REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "span 4 devices: True" in proc.stdout
    assert proc.stdout.strip().splitlines()[-1] == "FOUR_CHIP_OK"


def test_main_refuses_cpu_and_names_it():
    proc = _run(["chip_smoke.py"], REPO)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_lone_script_fails(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    proc = subprocess.run([sys.executable, "chip_smoke.py"],
                          capture_output=True, text=True, timeout=300,
                          cwd=tmp_path,
                          env=dict(os.environ, JAX_PLATFORMS="cpu",
                                   PYTHONPATH=""))
    assert proc.returncode != 0
    assert "repro package is missing" in proc.stderr
    assert '"ok"' not in proc.stdout


CACHE = textwrap.dedent("""
    import json, os
    import jax, jax.numpy as jnp
    from repro.launch.compile_cache import REPO_CACHE, use_compile_cache
    d = use_compile_cache()
    out = {"dir": d, "config": jax.config.jax_compilation_cache_dir,
           "repo": str(REPO_CACHE)}
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: x @ x.T + 1.0)(jnp.ones((32, 32))).block_until_ready()
        out["entries"] = os.listdir(d)
    print(json.dumps(out))
""")


def test_compile_cache_honours_env_dir(tmp_path):
    proc = _run(["-c", CACHE], tmp_path,
                JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["dir"] == out["config"] == str(tmp_path / "cc")
    assert out["entries"]


def test_compile_cache_defaults_to_repo_dir(tmp_path):
    env = _env()
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run([sys.executable, "-c", CACHE], capture_output=True,
                          text=True, timeout=300, cwd=tmp_path, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["dir"] == out["config"] == out["repo"] == str(REPO /
                                                             ".jax_cache")
