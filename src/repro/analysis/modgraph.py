"""RA301 — import-graph reachability over ``src/repro`` (AST pass).

Builds the module-level import graph by parsing every file under
``src/repro`` (no imports are executed) and reports modules unreachable
from the public entry points:

- ``repro.nng`` (the library API),
- ``repro.launch.*`` (the CLI drivers),
- ``repro.analysis.*`` (this analyzer),
- plus pseudo-roots for every ``repro.*`` module imported by scripts in
  ``examples/`` — a module only an example calls is live code, not dead
  code.

Test files are deliberately NOT roots: a module only its own test imports
is the definition of an LLM-seed leftover. Keeping one anyway (e.g. a
module reserved for a roadmap item) is a baseline entry, not a root.
"""
from __future__ import annotations

import ast
from pathlib import Path

from .diagnostics import Diagnostic

__all__ = ["module_imports", "build_import_graph", "reachable",
           "dead_modules", "lint_dead_modules"]

ROOT_PREFIXES = ("repro.nng", "repro.launch", "repro.analysis")


def _iter_py(src_root: Path):
    for p in sorted(src_root.rglob("*.py")):
        yield p


def _module_name(path: Path, src_root: Path) -> str:
    # src_root is the `repro` package directory itself
    rel = path.relative_to(src_root).with_suffix("")
    parts = [src_root.name] + list(rel.parts)
    if parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


def module_imports(path: Path, modname: str, known: set) -> set:
    """Modules from ``known`` that ``path`` imports (module-level or
    function-level; relative imports resolved against ``modname``)."""
    tree = ast.parse(path.read_text())
    pkg_parts = modname.split(".")
    out = set()

    def add(name: str):
        # longest known prefix: "repro.kernels.nng_tile" counts both as
        # itself and, implicitly, its parent packages' __init__ side
        parts = name.split(".")
        for k in range(len(parts), 0, -1):
            cand = ".".join(parts[:k])
            if cand in known:
                out.add(cand)
                return

    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                add(a.name)
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                base = pkg_parts[:len(pkg_parts) - node.level + 1] \
                    if path.name == "__init__.py" \
                    else pkg_parts[:len(pkg_parts) - node.level]
                mod = ".".join(base + ([node.module] if node.module else []))
            else:
                mod = node.module or ""
            if mod:
                add(mod)
            # "from pkg import sub" where pkg.sub is itself a module
            for a in node.names:
                if mod:
                    add(f"{mod}.{a.name}")
    return out


def build_import_graph(src_root: Path) -> dict:
    files = {p: _module_name(p, src_root) for p in _iter_py(src_root)}
    known = set(files.values())
    graph = {}
    for p, mod in files.items():
        deps = module_imports(p, mod, known)
        # a module implicitly executes its ancestor packages' __init__
        parts = mod.split(".")
        for k in range(1, len(parts)):
            deps.add(".".join(parts[:k]))
        graph.setdefault(mod, set()).update(deps - {mod})
    # package __init__ does NOT implicitly import submodules — only
    # explicit imports count, which is the point of the pass.
    return graph


def _script_roots(repo_root: Path, known: set) -> set:
    roots = set()
    d = repo_root / "examples"
    if d.is_dir():
        for p in sorted(d.rglob("*.py")):
            try:
                tree = ast.parse(p.read_text())
            except SyntaxError:
                continue
            for node in ast.walk(tree):
                names = []
                if isinstance(node, ast.Import):
                    names = [a.name for a in node.names]
                elif isinstance(node, ast.ImportFrom) and not node.level:
                    mod = node.module or ""
                    names = [mod] + [f"{mod}.{a.name}" for a in node.names]
                for name in names:
                    parts = name.split(".")
                    for k in range(len(parts), 0, -1):
                        cand = ".".join(parts[:k])
                        if cand in known:
                            roots.add(cand)
                            break
    return roots


def reachable(graph: dict, roots: set) -> set:
    seen = set()
    stack = [r for r in roots if r in graph]
    while stack:
        m = stack.pop()
        if m in seen:
            continue
        seen.add(m)
        stack.extend(graph.get(m, set()) - seen)
    return seen


def dead_modules(src_root: Path, repo_root: Path | None = None) -> list:
    src_root = Path(src_root)
    # src_root is <repo>/src/repro — examples/ lives at the repo root,
    # two levels up
    repo_root = Path(repo_root) if repo_root else src_root.parent.parent
    graph = build_import_graph(src_root)
    roots = {m for m in graph
             if any(m == p or m.startswith(p + ".") for p in ROOT_PREFIXES)}
    roots |= _script_roots(repo_root, set(graph))
    live = reachable(graph, roots)
    # pure packages (namespace __init__-only nodes) whose every submodule
    # is dead are reported via the submodules; skip the bare package name
    # when it has no file content beyond re-exports of dead members.
    return sorted(m for m in graph if m not in live and m != "repro")


def lint_dead_modules(src_root: Path, repo_root: Path | None = None
                      ) -> list[Diagnostic]:
    return [Diagnostic(
        "RA301", m,
        f"module '{m}' is unreachable from repro.nng / repro.launch / "
        f"repro.analysis and no example imports it")
        for m in dead_modules(src_root, repo_root)]
