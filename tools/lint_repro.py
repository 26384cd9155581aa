#!/usr/bin/env python
"""Repo-structure lint: AST checks for the two boundaries the codebase
routes through single modules (CI's ``analysis`` job runs this
on every push; ``python tools/lint_repro.py`` locally).

* ``jax.experimental.shard_map`` (the legacy API) is imported nowhere:
  every module uses ``repro.core.jax_compat.shard_map``, which fixes
  the manual-axes signature over ``jax.shard_map`` (DESIGN.md §2).
* The ``XLA_FLAGS --xla_force_host_platform_device_count`` env prepend
  may only appear in ``src/repro/launch/hostdevices.py`` — scattered
  prepends fight each other (last writer wins after jax initializes),
  so host-device-count setup is centralized there.

Exit 0 with ``REPO_LINT_OK`` when clean; one line per violation and
exit 1 otherwise.
"""
from __future__ import annotations

import ast
import os
import sys
from typing import List

HOSTDEV_HOME = os.path.join("src", "repro", "launch", "hostdevices.py")
ENV_NEEDLE = "xla_force_host_platform_device_count"


def _is_shard_map_import(node: ast.AST) -> bool:
    if isinstance(node, ast.Import):
        return any(a.name.startswith("jax.experimental.shard_map")
                   for a in node.names)
    if isinstance(node, ast.ImportFrom) and node.module:
        if node.module.startswith("jax.experimental.shard_map"):
            return True
        if node.module == "jax.experimental":
            return any(a.name == "shard_map" for a in node.names)
    return False


def _env_prepend_lines(tree: ast.AST, source: str) -> List[int]:
    # flag any string literal carrying the XLA flag (f-strings included
    # via their literal fragments) — assignments to os.environ with it
    # are exactly the prepends being centralized
    lines = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Constant) and isinstance(node.value, str) \
                and ENV_NEEDLE in node.value.lower():
            lines.append(node.lineno)
    return lines


def lint_file(path: str) -> List[str]:
    with open(path, encoding="utf-8") as f:
        source = f.read()
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [f"{path}:{e.lineno}: syntax error: {e.msg}"]
    rel = os.path.relpath(path)
    problems = []
    for node in ast.walk(tree):
        if _is_shard_map_import(node):
            problems.append(
                f"{rel}:{node.lineno}: legacy jax.experimental.shard_map "
                "imported — use repro.core.jax_compat.shard_map")
    if not rel.endswith(HOSTDEV_HOME):
        for lineno in _env_prepend_lines(tree, source):
            problems.append(
                f"{rel}:{lineno}: {ENV_NEEDLE} set outside "
                f"{HOSTDEV_HOME} — route host-device-count setup "
                "through launch/hostdevices.py")
    return problems


def main(argv=None) -> int:
    roots = (argv or sys.argv[1:]) or ["src", "tests", "benchmarks",
                                       "examples"]
    problems: List[str] = []
    n = 0
    for root in roots:
        if os.path.isfile(root):
            n += 1
            problems += lint_file(root)
            continue
        for dirpath, _, files in os.walk(root):
            for name in sorted(files):
                if name.endswith(".py"):
                    n += 1
                    problems += lint_file(os.path.join(dirpath, name))
    for p in problems:
        print(p, file=sys.stderr)
    if problems:
        return 1
    print(f"REPO_LINT_OK files={n}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
