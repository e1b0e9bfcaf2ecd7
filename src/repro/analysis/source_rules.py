"""Source-layer rules: repo-specific AST lint over ``src/repro``.

These encode hygiene rules the protocols were built to make possible:
the search path dispatches on protocol methods, never ``isinstance`` over
scorer/index classes (PR 1's whole point); jit-traced functions never
host-sync (``.item()`` / ``np.*`` on traced values forces a blocking
device->host copy per call); ``jax.debug.*`` never ships; the mesh and
shard_map APIs route through ``utils/jax_compat.py`` so one module
chooses their settings.

Each rule walks pre-parsed ASTs from a shared :class:`SourceTree`.
A violation can be waived for a specific line with a trailing
``# analysis: allow-<rule-tag>`` comment -- the waiver is greppable and
reviewed, unlike an allowlist buried here.
"""
from __future__ import annotations

import ast
import os
from typing import List, Tuple

from repro.analysis.registry import Rule, RuleResult

__all__ = ["SourceTree", "NoJaxDebug", "NoIsinstanceDispatch",
           "NoHostSyncInJit", "NoRawCompatAPIs", "DISPATCH_CLASSES"]

# Scorer / Index protocol classes: isinstance over any of these in hot-
# path modules is type dispatch the protocols exist to remove.
DISPATCH_CLASSES = frozenset({
    "LinearScorer", "GleanVecScorer", "QuantizedScorer",
    "GleanVecQuantizedScorer", "SortedGleanVecScorer",
    "SortedGleanVecQuantizedScorer", "FlatIndex", "IVFIndex",
    "GraphIndex", "ShardedIndex",
})

# Hot-path module prefixes (repo-relative, '/'-separated) where protocol
# dispatch is the law. ``kernels/__init__.py`` is deliberately NOT here:
# it is the one sanctioned scorer->kernel lowering boundary ("Index code
# never mentions kernels; it talks to scorers, and scorers lower here").
HOT_PATHS = ("core/search.py", "core/scorer.py", "index/", "serve/")

# jax.* attribute chains that must go through utils/jax_compat.py.
RAW_COMPAT_APIS = frozenset({
    "jax.make_mesh", "jax.set_mesh", "jax.shard_map",
    "jax.experimental.shard_map",
})
COMPAT_MODULE = "utils/jax_compat.py"


class SourceTree:
    """``src/repro`` parsed once: (relpath, source lines, ast) per file,
    shared by every source rule."""

    def __init__(self, root: str):
        self.root = root
        self.files: List[Tuple[str, List[str], ast.AST]] = []
        for dirpath, dirnames, filenames in sorted(os.walk(root)):
            dirnames.sort()
            for fn in sorted(filenames):
                if not fn.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fn)
                rel = os.path.relpath(path, root).replace(os.sep, "/")
                with open(path) as f:
                    src = f.read()
                try:
                    tree = ast.parse(src, filename=rel)
                except SyntaxError:
                    continue        # not this layer's problem
                self.files.append((rel, src.splitlines(), tree))

    @classmethod
    def of(cls, subject) -> "SourceTree":
        return subject if isinstance(subject, cls) else cls(subject)


def _attr_chain(node) -> str:
    """Dotted name of an attribute chain (``jax.debug.print`` ->
    "jax.debug.print"), or "" for non-name roots."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _waived(lines: List[str], lineno: int, tag: str) -> bool:
    ln = lines[lineno - 1] if 0 < lineno <= len(lines) else ""
    return f"# analysis: allow-{tag}" in ln


class _SourceRule(Rule):
    family = "source"
    tag = ""            # the allow-comment suffix

    def check(self, tree) -> RuleResult:
        tree = SourceTree.of(tree)
        findings = []
        for rel, lines, mod in tree.files:
            for lineno, msg in self.visit_file(rel, mod):
                if not _waived(lines, lineno, self.tag):
                    findings.append(f"{rel}:{lineno}: {msg}")
        if findings:
            return self._fail("; ".join(findings))
        return self._pass(f"{len(tree.files)} files clean")

    def visit_file(self, rel: str, mod: ast.AST):
        raise NotImplementedError


class NoJaxDebug(_SourceRule):
    """No ``jax.debug.*`` (print/breakpoint/callback) ships: they force
    host callbacks on every call of a compiled function."""

    name = "NoJaxDebug"
    tag = "jax-debug"
    contract = "no jax.debug.* call ships in src/repro"

    def visit_file(self, rel, mod):
        for node in ast.walk(mod):
            if isinstance(node, ast.Attribute):
                chain = _attr_chain(node)
                if chain.startswith("jax.debug."):
                    yield node.lineno, f"{chain} leftover"


class NoIsinstanceDispatch(_SourceRule):
    """No ``isinstance`` over Scorer/Index protocol classes in hot-path
    modules: dispatch goes through protocol methods, so index x scorer x
    placement stay orthogonal axes."""

    name = "NoIsinstanceDispatch"
    tag = "isinstance"
    contract = ("hot paths (core/search, core/scorer, index/, serve/, "
                "kernels/) never isinstance-dispatch on protocol classes")

    def visit_file(self, rel, mod):
        if not any(rel.startswith(p) for p in HOT_PATHS):
            return
        for node in ast.walk(mod):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id == "isinstance"
                    and len(node.args) == 2):
                continue
            t = node.args[1]
            names = [e for e in (t.elts if isinstance(t, ast.Tuple)
                                 else [t])]
            for e in names:
                nm = e.id if isinstance(e, ast.Name) else \
                    (e.attr if isinstance(e, ast.Attribute) else "")
                if nm in DISPATCH_CLASSES:
                    yield node.lineno, f"isinstance dispatch on {nm}"


class NoHostSyncInJit(_SourceRule):
    """Inside functions decorated with ``jax.jit`` (bare or through
    ``functools.partial``): no ``.item()``, no ``np.*`` / ``numpy.*``
    calls, no ``jax.device_get`` -- each forces a trace-time constant or
    a host sync. (Conservative by design: python ``float(...)`` over
    static shape arithmetic is legal and stays out of scope.)"""

    name = "NoHostSyncInJit"
    tag = "host-sync"
    contract = ("jit-traced function bodies never call .item(), np.*, "
                "or jax.device_get")

    @staticmethod
    def _is_jit_decorated(fn: ast.AST) -> bool:
        for dec in fn.decorator_list:
            chain = _attr_chain(dec)
            if chain in ("jax.jit", "jit"):
                return True
            if isinstance(dec, ast.Call):
                chain = _attr_chain(dec.func)
                if chain in ("jax.jit", "jit"):
                    return True
                if chain in ("functools.partial", "partial") and \
                        dec.args and _attr_chain(dec.args[0]) in (
                            "jax.jit", "jit"):
                    return True
        return False

    def visit_file(self, rel, mod):
        for fn in ast.walk(mod):
            if not isinstance(fn, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                continue
            if not self._is_jit_decorated(fn):
                continue
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                chain = _attr_chain(node.func)
                if chain.endswith(".item") and "." in chain:
                    yield node.lineno, \
                        f"{chain}() host sync in jitted {fn.name}"
                elif chain.startswith(("np.", "numpy.")):
                    yield node.lineno, \
                        f"{chain}() in jitted {fn.name}"
                elif chain == "jax.device_get":
                    yield node.lineno, \
                        f"jax.device_get in jitted {fn.name}"


class NoRawCompatAPIs(_SourceRule):
    """The mesh and shard_map APIs are used only through
    ``utils/jax_compat.py`` -- one module chooses their settings (Auto
    axis types, no replication check)."""

    name = "NoRawCompatAPIs"
    tag = "raw-compat"
    contract = ("jax.make_mesh / jax.set_mesh / jax.shard_map / "
                "jax.experimental.shard_map only inside utils/jax_compat")

    def visit_file(self, rel, mod):
        if rel == COMPAT_MODULE:
            return
        for node in ast.walk(mod):
            if isinstance(node, ast.Attribute):
                chain = _attr_chain(node)
                if chain in RAW_COMPAT_APIS:
                    yield node.lineno, \
                        f"{chain} bypasses utils/jax_compat"
            elif isinstance(node, (ast.Import, ast.ImportFrom)):
                names = []
                if isinstance(node, ast.ImportFrom) and node.module:
                    names = [f"{node.module}.{a.name}"
                             for a in node.names]
                else:
                    names = [a.name for a in node.names]
                for nm in names:
                    if nm in RAW_COMPAT_APIS or \
                            nm.startswith("jax.experimental.shard_map"):
                        yield node.lineno, \
                            f"import {nm} bypasses utils/jax_compat"
