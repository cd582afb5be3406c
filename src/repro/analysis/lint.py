"""Custom AST lint for the repo's recurring bug classes.

Each rule targets a failure mode that produced (or would have prevented)
an actual bugfix in the PR history:

  ========  ==============================================================
  rule      what / why
  ========  ==============================================================
  REPRO001  ``jax.sharding`` / ``shard_map`` imported or referenced
            outside ``compat.py``.  JAX has moved ``shard_map`` and the
            sharding API between releases; direct imports are the
            API-drift class behind an earlier sharding-constraint
            no-op.  All access goes through ``repro.compat``.
  REPRO002  blanket ``except Exception: pass`` (or bare ``except:``).
            Swallowing everything hid the PR 3 constraint no-op; catch
            the concrete types and record or re-raise.
  REPRO003  unseeded global-RNG calls (``np.random.rand`` etc. /
            ``from numpy.random import shuffle``) in ``core/`` +
            ``sparse/`` schedule-building code.  Plans must be
            deterministic — use ``np.random.default_rng(seed)``.
  REPRO004  host-sync idioms in solver paths: ``.item()`` and
            ``jax.device_get(...)`` in ``core/`` + ``sparse/``, and
            ``float()``/``int()``/``bool()`` /
            ``np.asarray(...)``/``np.array(...)`` on traced values
            inside explicitly ``@jit``-decorated functions.  Each
            forces a device round-trip per CG iteration (the numpy
            coercions additionally fail with a ConcretizationError on
            abstract values — host plan-building is where they belong,
            and that code is never jitted).
  ========  ==============================================================

Pure ``ast`` — no imports of the linted code.  ``ALLOWLIST`` maps path
suffixes to the rule codes permitted there (``compat.py`` is the single sanctioned home of
the sharding imports).
"""
from __future__ import annotations

import ast
from pathlib import Path
from typing import Iterable

from .diagnostics import Report

LINT_RULES: dict[str, str] = {
    "REPRO001": "jax.sharding/shard_map used outside compat.py",
    "REPRO002": "blanket 'except Exception: pass' swallows errors",
    "REPRO003": "unseeded global RNG in schedule-building code",
    "REPRO004": "host-sync (.item()/float()/np.asarray/device_get) in "
                "jitted solver paths",
}

# path-suffix -> codes sanctioned there.  Keep this near-empty: compat.py
# exists precisely so nothing else needs an entry.
ALLOWLIST: dict[str, frozenset[str]] = {
    "repro/compat.py": frozenset({"REPRO001"}),
}

_SEEDED_RNG = {"default_rng", "Generator", "SeedSequence", "RandomState",
               "Philox", "PCG64", "MT19937", "bit_generator"}
_JIT_NAMES = {"jit"}          # matches jit, jax.jit, partial(jax.jit, ...)
_HOST_COERCE = {"float", "int", "bool"}
# numpy materializations: legitimate all over host plan-building, a host
# sync (or ConcretizationError) on traced values — flagged inside jit only
_NP_COERCE = {"np.asarray", "np.array", "numpy.asarray", "numpy.array"}


def _dotted(node: ast.AST) -> str:
    """'jax.sharding.Mesh' for an Attribute/Name chain, '' otherwise."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _is_sharding_module(mod: str) -> bool:
    """``jax.sharding[.*]`` or any ``jax.*.shard_map[.*]`` module."""
    parts = mod.split(".")
    return parts[0] == "jax" and (parts[1:2] == ["sharding"]
                                  or "shard_map" in parts[1:])


def _is_jit_decorator(dec: ast.AST) -> bool:
    name = _dotted(dec)
    if name.split(".")[-1] in _JIT_NAMES:
        return True
    if isinstance(dec, ast.Call):              # partial(jax.jit, ...) /
        if _is_jit_decorator(dec.func):        # jax.jit(static_argnums=..)
            return True
        return any(_is_jit_decorator(a) for a in dec.args)
    return False


class _Linter(ast.NodeVisitor):
    def __init__(self, path: Path, rel: str, rep: Report,
                 allowed: frozenset[str]):
        self.path, self.rel, self.rep, self.allowed = path, rel, rep, allowed
        parts = Path(rel).parts
        self.solver_scope = "core" in parts or "sparse" in parts
        self.jit_depth = 0

    def _add(self, code: str, node: ast.AST, message: str) -> None:
        if code in self.allowed:
            return
        self.rep.add(code, message,
                     where=f"{self.rel}:{getattr(node, 'lineno', 0)}")

    # -- REPRO001 -----------------------------------------------------
    def visit_Import(self, node: ast.Import) -> None:
        for alias in node.names:
            if _is_sharding_module(alias.name):
                self._add("REPRO001", node,
                          f"import {alias.name}: use repro.compat instead "
                          "of importing jax.sharding/shard_map directly")
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        mod = node.module or ""
        if _is_sharding_module(mod) or (
                mod in ("jax.experimental", "jax")
                and any(a.name in ("shard_map", "sharding")
                        for a in node.names)):
            self._add("REPRO001", node,
                      f"from {mod} import "
                      f"{', '.join(a.name for a in node.names)}: use "
                      "repro.compat instead")
        if mod == "numpy.random" or mod.startswith("numpy.random."):
            bad = [a.name for a in node.names
                   if a.name not in _SEEDED_RNG]
            if bad and self.solver_scope:
                self._add("REPRO003", node,
                          f"from numpy.random import {', '.join(bad)}: "
                          "global-RNG functions are unseeded; use "
                          "np.random.default_rng(seed)")
        self.generic_visit(node)

    def visit_Attribute(self, node: ast.Attribute) -> None:
        name = _dotted(node)
        if name.startswith("jax.sharding.") or name == "jax.sharding":
            self._add("REPRO001", node,
                      f"{name}: use repro.compat instead of the "
                      "jax.sharding namespace")
            return          # don't re-flag the nested jax.sharding chain
        self.generic_visit(node)

    # -- REPRO002 -----------------------------------------------------
    def visit_ExceptHandler(self, node: ast.ExceptHandler) -> None:
        broad = node.type is None or (
            isinstance(node.type, ast.Name)
            and node.type.id in ("Exception", "BaseException"))

        def _noop(s: ast.stmt) -> bool:   # `pass` or a bare `...`
            return isinstance(s, ast.Pass) or (
                isinstance(s, ast.Expr)
                and isinstance(s.value, ast.Constant)
                and s.value.value is Ellipsis)

        only_pass = all(_noop(s) for s in node.body)
        if broad and only_pass:
            what = ("bare except" if node.type is None
                    else f"except {node.type.id}")
            self._add("REPRO002", node,
                      f"{what}: pass — swallows every error; catch the "
                      "concrete exception types and record or re-raise")
        self.generic_visit(node)

    # -- REPRO003 / REPRO004 ------------------------------------------
    def visit_Call(self, node: ast.Call) -> None:
        name = _dotted(node.func)
        if self.solver_scope and name:
            parts = name.split(".")
            if len(parts) >= 3 and parts[-2] == "random" \
                    and parts[0] in ("np", "numpy") \
                    and parts[-1] not in _SEEDED_RNG:
                self._add("REPRO003", node,
                          f"{name}(): unseeded global RNG makes plan "
                          "construction nondeterministic; use "
                          "np.random.default_rng(seed)")
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr == "item" and not node.args \
                and self.solver_scope:
            self._add("REPRO004", node,
                      ".item(): host sync — forces a device round-trip "
                      "in the solver path; keep reductions on device")
        if self.jit_depth and isinstance(node.func, ast.Name) \
                and node.func.id in _HOST_COERCE and node.args:
            self._add("REPRO004", node,
                      f"{node.func.id}() on a traced value inside a "
                      "jitted function: host sync (ConcretizationError "
                      "at best, per-step round-trip at worst)")
        if self.jit_depth and name in _NP_COERCE and node.args:
            self._add("REPRO004", node,
                      f"{name}() inside a jitted function materializes "
                      "the traced value on host; use jnp for on-device "
                      "work and keep numpy in plan construction")
        if name.split(".")[-1] == "device_get" \
                and (self.solver_scope or self.jit_depth):
            self._add("REPRO004", node,
                      f"{name}(): explicit device->host transfer in the "
                      "solver path; keep reductions on device and fetch "
                      "results once after the solve")
        self.generic_visit(node)

    def _visit_func(self, node) -> None:
        jitted = any(_is_jit_decorator(d) for d in node.decorator_list)
        self.jit_depth += jitted
        self.generic_visit(node)
        self.jit_depth -= jitted

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func


def _iter_py(paths: Iterable[str | Path]):
    for p in paths:
        p = Path(p)
        if p.is_dir():
            yield from sorted(q for q in p.rglob("*.py")
                              if not any(part.startswith(".")
                                         for part in q.parts))
        elif p.suffix == ".py":
            yield p


def lint_paths(paths: Iterable[str | Path], *,
               allowlist: dict[str, frozenset[str]] | None = None,
               root: str | Path | None = None) -> Report:
    """Lint every ``.py`` file under ``paths``; returns a :class:`Report`
    whose diagnostics carry ``rule [path:line]: message``."""
    allow = ALLOWLIST if allowlist is None else allowlist
    root = Path(root) if root is not None else Path.cwd()
    rep = Report(subject="lint")
    n = 0
    for path in _iter_py(paths):
        n += 1
        try:
            rel = str(path.resolve().relative_to(root.resolve()))
        except ValueError:
            rel = str(path)
        rel = rel.replace("\\", "/")
        allowed = frozenset().union(
            *(codes for suffix, codes in allow.items()
              if rel.endswith(suffix)))
        try:
            tree = ast.parse(path.read_text(), filename=str(path))
        except SyntaxError as e:
            rep.add("REPRO000", f"syntax error: {e.msg}",
                    where=f"{rel}:{e.lineno}")
            continue
        _Linter(path, rel, rep, allowed).visit(tree)
    rep.info["files"] = n
    return rep
