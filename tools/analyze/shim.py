"""Pass 2 — shim enforcement.

Every shard_map use must route through the one wrapper in
``src/repro/distribution/context.py`` (the single place that sets
``check_vma``).  This pass forbids, anywhere else in the repo:

* ``import jax.experimental.shard_map`` (any form)
* ``from jax.experimental import shard_map`` / ``from
  jax.experimental.shard_map import ...``
* ``from jax import shard_map``
* attribute references ``jax.shard_map`` or
  ``jax.experimental.shard_map`` on an imported jax alias
"""

from __future__ import annotations

import ast
import os
from typing import List, Optional, Sequence

from .common import Finding, Module, iter_py_files, relpath, REPO_ROOT
from .rules import SHIM_IMPORT

ALLOWED = ("src/repro/distribution/context.py",)

# Directories worth scanning: everything that contains repo Python.
SCAN_SUBDIRS = ("src", "tests", "benchmarks", "examples", "launch", "tools")


def _check_module(mod: Module) -> List[Finding]:
    out: List[Finding] = []

    def bad(node: ast.AST, what: str) -> None:
        out.append(Finding(
            SHIM_IMPORT, mod.rel, getattr(node, "lineno", 1),
            "%s — route through repro.distribution.context.shard_map"
            % what))

    # Which local names alias the jax package (import jax [as j]).
    jax_aliases = {alias for alias, target in mod.import_alias.items()
                   if target == "jax" or target.startswith("jax.")}

    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name.startswith("jax.experimental.shard_map"):
                    bad(node, "direct import of jax.experimental.shard_map")
        elif isinstance(node, ast.ImportFrom):
            m = node.module or ""
            if m.startswith("jax.experimental.shard_map"):
                bad(node, "direct import from jax.experimental.shard_map")
            elif m == "jax.experimental":
                for a in node.names:
                    if a.name == "shard_map":
                        bad(node, "direct import of "
                            "jax.experimental.shard_map")
            elif m == "jax":
                for a in node.names:
                    if a.name == "shard_map":
                        bad(node, "direct import of jax.shard_map")
        elif isinstance(node, ast.Attribute) and node.attr == "shard_map":
            # jax.shard_map / jax.experimental.shard_map / j.shard_map
            base = node.value
            if isinstance(base, ast.Name) and base.id in jax_aliases:
                bad(node, "direct reference to jax.shard_map")
            elif (isinstance(base, ast.Attribute)
                  and base.attr == "experimental"
                  and isinstance(base.value, ast.Name)
                  and base.value.id in jax_aliases):
                bad(node, "direct reference to jax.experimental.shard_map")
    return out


def run(root: str = REPO_ROOT,
        files: Optional[Sequence[str]] = None) -> List[Finding]:
    findings: List[Finding] = []
    if files is None:
        files = []
        for sub in SCAN_SUBDIRS:
            if os.path.isdir(os.path.join(root, sub)):
                files.extend(iter_py_files(root, (sub,)))
    for path in files:
        rel = relpath(path, root)
        if rel in ALLOWED:
            continue
        try:
            mod = Module(path, root)
        except SyntaxError:
            continue
        findings.extend(_check_module(mod))
    return findings
