"""CLI contract tests for ``python -m repro.analysis`` (ISSUE 8
satellites 2 + 6): the unified exit code (nonzero iff *any* pass reported
a diagnostic), the machine-readable ``--format=json`` / ``--format=github``
output, and the ``trace --out`` CI artifact.

In-process ``main(argv)`` calls cover the format/exit matrix cheaply;
two real subprocesses pin down the actual shell contract CI depends on.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro import compat
from repro.analysis.__main__ import main

REPO = Path(__file__).resolve().parents[1]

@pytest.fixture()
def offender_dir(tmp_path):
    bad = tmp_path / "mod.py"
    bad.write_text(textwrap.dedent("""\
        from jax.sharding import Mesh
    """))
    return tmp_path


# ------------------------------------------------------------- exit codes

def test_lint_clean_exits_zero(tmp_path, capsys):
    ok = tmp_path / "fine.py"
    ok.write_text("x = 1\n")
    assert main(["lint", str(ok)]) == 0
    assert "0 failing" in capsys.readouterr().out


def test_lint_offender_exits_nonzero(offender_dir, capsys):
    assert main(["lint", str(offender_dir)]) == 1
    assert "REPRO001" in capsys.readouterr().out


def test_verify_clean_exits_zero(capsys):
    assert main(["verify", "--n", "80", "--fanouts", "2,2",
                 "--generator", "grid_2d"]) == 0
    out = capsys.readouterr().out
    assert "0 failing" in out


def test_trace_clean_exits_zero(capsys):
    assert main(["trace", "--backend", "coo", "--backend", "dist_halo",
                 "--n", "64"]) == 0
    out = capsys.readouterr().out
    assert "flop/it" in out and "0 failing" in out


# ---------------------------------------------------------------- formats

def test_lint_json_format(offender_dir, capsys):
    rc = main(["lint", str(offender_dir), "--format=json"])
    assert rc == 1
    reports = json.loads(capsys.readouterr().out)
    assert isinstance(reports, list) and not reports[0]["ok"]
    d = reports[0]["diagnostics"][0]
    assert d["code"] == "REPRO001"
    assert d["where"].endswith("mod.py:1")


def test_lint_github_format(offender_dir, capsys, monkeypatch):
    monkeypatch.chdir(offender_dir)
    rc = main(["lint", str(offender_dir), "--format=github"])
    assert rc == 1
    out = capsys.readouterr().out
    assert "::error file=mod.py,line=1::REPRO001:" in out


def test_trace_json_and_artifact(tmp_path, capsys):
    art = tmp_path / "trace_audit.json"
    rc = main(["trace", "--backend", "dist_halo", "--n", "64",
               "--format=json", "--out", str(art)])
    assert rc == 0
    console = json.loads(capsys.readouterr().out)
    on_disk = json.loads(art.read_text())
    assert console == on_disk
    rep = on_disk[0]
    assert rep["ok"] and rep["subject"].startswith("dist_halo")
    cost = rep["info"]["cost_cg"]
    assert cost["flops_per_iter"] > 0
    assert len(cost["comm_payload_bytes_lvl"]) == 1


def test_trace_github_format_on_failure(capsys, monkeypatch):
    """Non-file diagnostics still come out as ::error annotations.  A
    trace failure is simulated by auditing a mutated schedule through the
    plain Report path the formatter consumes."""
    from repro.analysis.__main__ import _print_github
    from repro.analysis.diagnostics import Report

    rep = Report(subject="dist_halo grid_2d")
    rep.add("TRACE002", "staged permutation differs",
            where="level 0 round 1")
    _print_github([rep])
    out = capsys.readouterr().out
    assert out.startswith("::error::dist_halo grid_2d [level 0 round 1]:")
    assert "TRACE002" in out


# ------------------------------------------------------------ subprocesses

def _run_cli(args, cwd=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        capture_output=True, text=True, env=env, cwd=cwd or REPO,
        timeout=600)


def test_subprocess_exit_code_contract(offender_dir):
    bad = _run_cli(["lint", str(offender_dir)])
    assert bad.returncode == 1, bad.stderr
    assert "REPRO001" in bad.stdout
    good = _run_cli(["lint", str(REPO / "src" / "repro" / "analysis")])
    assert good.returncode == 0, good.stderr + good.stdout


def test_subprocess_trace_smoke(tmp_path):
    art = tmp_path / "audit.json"
    res = _run_cli(["trace", "--backend", "coo", "--backend", "dist_hier",
                    "--n", "64", "--fanouts", "2,2", "--out", str(art)])
    assert res.returncode == 0, res.stderr + res.stdout
    reports = json.loads(art.read_text())
    assert all(r["ok"] for r in reports)
    assert {r["subject"].split()[0] for r in reports} == \
        {"coo", "dist_hier"}
