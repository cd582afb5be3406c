"""AST lint rule tests (ISSUE 6 tentpole, lint half).

Each rule gets a tmp_path offender file that must be flagged with the
right rule ID and line, plus a negative twin that must stay clean; the
final test lints the real ``src/`` tree and requires zero findings —
the satellite-1 migration contract (all sharding imports flow through
``compat.py``, which is the single allowlisted file).
"""
import textwrap
from pathlib import Path

import pytest

from repro.analysis.lint import LINT_RULES, lint_paths

SRC = Path(__file__).resolve().parents[1] / "src"


def _lint_snippet(tmp_path, code, rel="mod.py"):
    f = tmp_path / rel
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(textwrap.dedent(code))
    return lint_paths([f], root=tmp_path)


def _where(d):
    path, _, line = d.where.rpartition(":")
    return path, int(line)


def _codes_lines(rep):
    return {(d.code, _where(d)[1]) for d in rep.diagnostics}


def test_rule_table_is_complete():
    assert set(LINT_RULES) == {"REPRO001", "REPRO002", "REPRO003",
                               "REPRO004"}
    for code, desc in LINT_RULES.items():
        assert desc and code.startswith("REPRO")


# ---------------------------------------------------------------- REPRO001

def test_repro001_from_import(tmp_path):
    rep = _lint_snippet(tmp_path, """\
        from jax.sharding import Mesh, PartitionSpec
    """)
    assert ("REPRO001", 1) in _codes_lines(rep)


def test_repro001_plain_import_and_attribute(tmp_path):
    rep = _lint_snippet(tmp_path, """\
        import jax.sharding
        import jax

        def f():
            return jax.sharding.Mesh((), ())
    """)
    codes = _codes_lines(rep)
    assert ("REPRO001", 1) in codes
    assert ("REPRO001", 5) in codes
    # the attribute chain is flagged once, not once per nesting level
    assert sum(1 for c, ln in codes if c == "REPRO001" and ln == 5) == 1


def test_repro001_shard_map_import(tmp_path):
    rep = _lint_snippet(tmp_path, """\
        from jax.experimental import shard_map
    """)
    assert ("REPRO001", 1) in _codes_lines(rep)


def test_repro001_compat_is_allowlisted(tmp_path):
    rep = _lint_snippet(tmp_path, """\
        import jax
        Mesh = jax.sharding.Mesh
    """, rel="repro/compat.py")
    assert rep.ok, str(rep)


def test_repro001_allowlist_is_per_rule(tmp_path):
    # compat.py is allowlisted for REPRO001 only; other rules still fire
    rep = _lint_snippet(tmp_path, """\
        try:
            x = 1
        except Exception:
            pass
    """, rel="repro/compat.py")
    assert {d.code for d in rep.diagnostics} == {"REPRO002"}


# ---------------------------------------------------------------- REPRO002

def test_repro002_swallowed_exception(tmp_path):
    rep = _lint_snippet(tmp_path, """\
        try:
            risky()
        except Exception:
            pass
        try:
            risky()
        except:
            ...
    """)
    codes = _codes_lines(rep)
    assert ("REPRO002", 3) in codes
    assert ("REPRO002", 7) in codes


def test_repro002_negative(tmp_path):
    rep = _lint_snippet(tmp_path, """\
        import logging
        try:
            risky()
        except Exception:
            logging.exception("boom")
        try:
            risky()
        except ValueError:
            pass
    """)
    assert rep.ok, str(rep)


# ---------------------------------------------------------------- REPRO003

def test_repro003_unseeded_rng_in_core(tmp_path):
    rep = _lint_snippet(tmp_path, """\
        import numpy as np
        x = np.random.rand(4)
        np.random.seed(0)
    """, rel="repro/core/foo.py")
    codes = _codes_lines(rep)
    assert ("REPRO003", 2) in codes
    assert ("REPRO003", 3) in codes


def test_repro003_scoped_to_solver_modules(tmp_path):
    # same code outside core/ or sparse/ is not the solver's concern
    rep = _lint_snippet(tmp_path, """\
        import numpy as np
        x = np.random.rand(4)
    """, rel="repro/launch/foo.py")
    assert rep.ok, str(rep)


def test_repro003_seeded_generator_is_fine(tmp_path):
    rep = _lint_snippet(tmp_path, """\
        import numpy as np
        rng = np.random.default_rng(0)
        x = rng.random(4)
    """, rel="repro/sparse/foo.py")
    assert rep.ok, str(rep)


# ---------------------------------------------------------------- REPRO004

def test_repro004_item_in_solver(tmp_path):
    rep = _lint_snippet(tmp_path, """\
        def step(r):
            return r.item()
    """, rel="repro/sparse/foo.py")
    assert ("REPRO004", 2) in _codes_lines(rep)


def test_repro004_float_inside_jit(tmp_path):
    rep = _lint_snippet(tmp_path, """\
        import jax
        from functools import partial

        @jax.jit
        def f(x):
            return float(x)

        @partial(jax.jit, static_argnums=0)
        def g(n, x):
            return int(x)

        def h(x):
            return float(x)   # not jitted: fine
    """)
    codes = _codes_lines(rep)
    assert ("REPRO004", 6) in codes
    assert ("REPRO004", 10) in codes
    assert not any(ln == 13 for _, ln in codes)


def test_repro004_np_asarray_inside_jit(tmp_path):
    rep = _lint_snippet(tmp_path, """\
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            return np.asarray(x)

        @jax.jit
        def g(x):
            return np.array(x)
    """)
    codes = _codes_lines(rep)
    assert ("REPRO004", 6) in codes
    assert ("REPRO004", 10) in codes


def test_repro004_np_asarray_outside_jit_is_fine(tmp_path):
    # host plan-building is where np.asarray belongs — even in sparse/
    rep = _lint_snippet(tmp_path, """\
        import numpy as np

        def build_plan(edges):
            return np.asarray(edges)
    """, rel="repro/sparse/foo.py")
    assert rep.ok, str(rep)


def test_repro004_device_get_in_solver(tmp_path):
    rep = _lint_snippet(tmp_path, """\
        import jax

        def fetch(y):
            return jax.device_get(y)
    """, rel="repro/sparse/foo.py")
    assert ("REPRO004", 4) in _codes_lines(rep)


def test_repro004_device_get_inside_jit_anywhere(tmp_path):
    rep = _lint_snippet(tmp_path, """\
        import jax

        @jax.jit
        def step(y):
            return jax.device_get(y)
    """, rel="repro/launch/foo.py")
    assert ("REPRO004", 5) in _codes_lines(rep)


def test_repro004_device_get_outside_solver_not_jitted_is_fine(tmp_path):
    rep = _lint_snippet(tmp_path, """\
        import jax

        def report(y):
            return jax.device_get(y)
    """, rel="repro/launch/foo.py")
    assert rep.ok, str(rep)


# ----------------------------------------------------------------- corpus

def test_syntax_error_reported_not_raised(tmp_path):
    rep = _lint_snippet(tmp_path, "def broken(:\n")
    assert {d.code for d in rep.diagnostics} == {"REPRO000"}


def test_real_source_tree_is_clean():
    rep = lint_paths([SRC])
    assert rep.ok, "migrated tree must lint clean:\n" + str(rep)
    assert rep.info["files"] > 50


def test_reintroduced_violation_has_file_and_line(tmp_path):
    rep = _lint_snippet(tmp_path, """\
        from jax.sharding import NamedSharding
    """, rel="repro/models/new_model.py")
    assert not rep.ok
    d = rep.diagnostics[0]
    assert d.code == "REPRO001"
    path, line = _where(d)
    assert path.endswith("new_model.py")
    assert line == 1
    assert "compat" in d.message


@pytest.mark.parametrize("code", sorted(LINT_RULES))
def test_every_rule_has_a_description(code):
    assert len(LINT_RULES[code]) > 10
