"""Dry-run machinery smoke test — subprocess with 16 forced host devices and
a reduced 2x2 mesh + smoke configs (the production 512-device sweep lives in
experiments/, driven by launch/dryrun.py)."""
import json
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import json
    import jax
    import numpy as np
    from repro.compat import make_mesh
    from repro.configs.registry import get_config
    from repro.launch.dryrun import _lower
    from repro.launch.roofline import analyze_compiled

    mesh = make_mesh((2, 2), ("data", "model"))
    out = {}
    for arch, mode, B, S in [
        ("qwen1.5-0.5b", "train", 4, 64),
        ("mamba2-130m", "decode", 4, 128),
        ("olmoe-1b-7b", "prefill", 4, 64),
        ("whisper-tiny", "train", 4, 64),
        ("recurrentgemma-2b", "decode", 4, 128),
    ]:
        cfg = get_config(arch, smoke=True)
        lowered, compiled = _lower(cfg, mode, B, S, mesh)
        rec = analyze_compiled(lowered, compiled)
        out[f"{arch}:{mode}"] = {
            "flops": rec["hlo_flops"], "bytes": rec["hlo_bytes"],
            "coll": sum(rec["collectives"].values()),
        }
    # multi-pod-shaped mesh too
    mesh3 = make_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = get_config("qwen1.5-0.5b", smoke=True)
    _, compiled = _lower(cfg, "train", 8, 64, mesh3)
    out["multipod"] = {"ok": True}
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def results():
    proc = subprocess.run([sys.executable, "-c", SCRIPT],
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_all_modes_compile(results):
    for key in ("qwen1.5-0.5b:train", "mamba2-130m:decode",
                "olmoe-1b-7b:prefill", "whisper-tiny:train",
                "recurrentgemma-2b:decode"):
        assert key in results
        assert results[key]["flops"] > 0
        assert results[key]["bytes"] > 0


def test_sharded_program_has_collectives(results):
    assert results["qwen1.5-0.5b:train"]["coll"] > 0


def test_multipod_mesh_compiles(results):
    assert results["multipod"]["ok"]
