"""The shape the package has, held by reading its source (no import of
the product, no device): the data plane's packages import only what
ranks below them, every ``VOLSYNC_*`` option has a reader, every metric
family has a writer, and the cluster substrate loads no fault injector.
"""

import ast
import functools
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
PKG = REPO / "volsync_tpu"

#: The data plane, bottom to top: a package imports from those before it.
COLUMN = ("obs", "io", "ops", "objstore", "repo", "engine", "parallel",
          "service")
#: Modules any layer may import: they import nothing of the column.
ROOTS = {"analysis", "envflags", "metrics", "resilience", "compile_cache",
         "version"}
#: What drives the data plane and is never imported by it.
CONTROL_PLANE = {"api", "cluster", "controller", "movers", "cli", "operator"}

#: The arrows that point up today, (file, imported module, imported
#: name) -> the debt that removes it. The list only shrinks:
#: ``test_every_listed_exception_still_exists`` fails on a stale entry.
EXCEPTIONS = {
    ("repo/repository.py", "volsync_tpu.engine.chunker",
     "verify_blob_batch"):
        "ROADMAP Design: `verify_blob_batch` lives a layer too high",
    ("repo/scrub.py", "volsync_tpu.engine.chunker", "verify_blob_batch"):
        "ROADMAP Design: `verify_blob_batch` lives a layer too high",
    ("engine/backup.py", "volsync_tpu.movers", "common"):
        "ROADMAP Design 10: the planner's stats book lives in movers/",
}


@functools.lru_cache(maxsize=None)
def _tree(path: Path) -> ast.Module:
    return ast.parse(path.read_text(encoding="utf-8"), filename=str(path))


def _package_imports(path: Path):
    """Every ``volsync_tpu`` import of a file, at module level or inside
    a function: (module, imported name or None, line)."""
    here = ["volsync_tpu", *path.relative_to(PKG).parts[:-1]]
    for node in ast.walk(_tree(path)):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name.split(".")[0] == "volsync_tpu":
                    yield alias.name, None, node.lineno
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                base = here[: len(here) - node.level + 1]
                module = ".".join(base + ([node.module] if node.module
                                          else []))
            else:
                module = node.module or ""
            if module.split(".")[0] != "volsync_tpu":
                continue
            for alias in node.names:
                yield module, alias.name, node.lineno


def _arrows(pkg: str):
    """(file, module, name, line, target package) of every import a
    package makes of another package of ``volsync_tpu``."""
    for path in sorted((PKG / pkg).rglob("*.py")):
        rel = path.relative_to(PKG).as_posix()
        for module, name, line in _package_imports(path):
            # ``from volsync_tpu import x`` reaches x itself
            target = module.split(".")[1] if "." in module else name
            if target is not None and target != pkg:
                yield rel, module, name, line, target


@pytest.mark.parametrize("pkg", COLUMN)
def test_a_data_plane_package_imports_only_below_itself(pkg):
    below = set(COLUMN[: COLUMN.index(pkg)]) | ROOTS
    wrong = [
        f"{rel}:{line} imports {module}"
        + (f".{name}" if name else "")
        + (" (control plane)" if target in CONTROL_PLANE else "")
        for rel, module, name, line, target in _arrows(pkg)
        if target not in below and (rel, module, name) not in EXCEPTIONS]
    assert not wrong, (
        f"volsync_tpu/{pkg} may import {sorted(below)} only:\n  "
        + "\n  ".join(wrong))


def test_every_listed_exception_still_exists():
    found = {(rel, module, name)
             for pkg in COLUMN
             for rel, module, name, _line, _target in _arrows(pkg)}
    gone = sorted(set(EXCEPTIONS) - found)
    assert not gone, f"repaired and still listed in EXCEPTIONS: {gone}"


def test_the_service_client_imports_no_server_and_no_engine():
    """A mover on a CPU node links against ``service/client.py``: the
    wire's names come from the leaf ``service/wire.py``, not from the
    server, whose import loads the batcher, the scheduler and the
    device programs."""
    for name in ("client.py", "wire.py"):
        wrong = [f"{name}:{line} imports {module}"
                 for module, _name, line in _package_imports(
                     PKG / "service" / name)
                 if module.split(".")[1:2] in (["ops"], ["engine"])
                 or module == "volsync_tpu.service.server"]
        assert not wrong, wrong
    assert not list(_package_imports(PKG / "service" / "wire.py"))


def test_the_cluster_package_loads_no_fault_injector():
    """Every mover entry and every cell imports
    ``volsync_tpu.cluster.runner``; the store's fault injector is for
    whoever arms it, not for them."""
    env = {**os.environ, "PYTHONPATH": os.pathsep.join(
        p for p in (str(REPO), os.environ.get("PYTHONPATH")) if p)}
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys, volsync_tpu.cluster.runner; "
         "print('volsync_tpu.objstore.faultstore' in sys.modules)"],
        env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "False"


# -- options and metric families ------------------------------------------

_OPTION = re.compile(r"VOLSYNC_[A-Z0-9_]+")


def _options():
    """The accessors of ``envflags.py`` that read a ``VOLSYNC_*`` name,
    in source order, then the names it catalogues that no accessor
    reads (another module reads such a name through ``env_bool`` and
    friends)."""
    path = PKG / "envflags.py"
    accessors, read = [], set()
    for node in _tree(path).body:
        if not isinstance(node, ast.FunctionDef):
            continue
        names = {c.value for c in ast.walk(node)
                 if isinstance(c, ast.Constant) and isinstance(c.value, str)
                 and _OPTION.fullmatch(c.value)}
        if names:
            accessors.append(node.name)
            read |= names
    bare = sorted(set(_OPTION.findall(path.read_text())) - read)
    return accessors + bare


def _reader_files():
    files = [p for d in ("volsync_tpu", "benchmark", "scripts")
             for p in sorted((REPO / d).rglob("*.py"))]
    files.append(REPO / "chip_smoke.py")
    return [p for p in files if p != PKG / "envflags.py"]


@functools.lru_cache(maxsize=None)
def _envflags_uses():
    """What the tree outside ``envflags.py`` takes from it: attribute
    reads on the module under any alias, names imported from it and
    loaded, and ``VOLSYNC_*`` literals."""
    used = set()
    for path in _reader_files():
        tree = _tree(path)
        modules, names = set(), {}
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) \
                    and node.module == "volsync_tpu":
                modules |= {a.asname or a.name for a in node.names
                            if a.name == "envflags"}
            elif isinstance(node, ast.ImportFrom) \
                    and node.module == "volsync_tpu.envflags":
                names.update({a.asname or a.name: a.name
                              for a in node.names})
            elif isinstance(node, ast.Import):
                modules |= {a.asname for a in node.names
                            if a.name == "volsync_tpu.envflags"
                            and a.asname}
        for node in ast.walk(tree):
            if isinstance(node, ast.Attribute) \
                    and isinstance(node.value, ast.Name) \
                    and node.value.id in modules:
                used.add(node.attr)
            elif isinstance(node, ast.Name) and node.id in names \
                    and isinstance(node.ctx, ast.Load):
                used.add(names[node.id])
            elif isinstance(node, ast.Constant) \
                    and isinstance(node.value, str) \
                    and _OPTION.fullmatch(node.value):
                used.add(node.value)
    return used


@pytest.mark.parametrize("option", _options())
def test_every_option_has_a_reader(option):
    assert option in _envflags_uses(), (
        f"envflags.{option}: no file of volsync_tpu/, benchmark/, "
        f"scripts/ or chip_smoke.py reads it; an option nothing reads "
        f"goes")


def _metric_families():
    """The attributes of ``Metrics`` bound to a prometheus family."""
    return [node.targets[0].attr
            for node in ast.walk(_tree(PKG / "metrics.py"))
            if isinstance(node, ast.Assign) and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Attribute)
            and isinstance(node.targets[0].value, ast.Name)
            and node.targets[0].value.id == "self"
            and isinstance(node.value, ast.Call)
            and getattr(node.value.func, "id", None)
            in ("Counter", "Gauge", "Histogram")]


@functools.lru_cache(maxsize=None)
def _metric_uses():
    """Attributes read off anything named like a ``Metrics`` object
    (``GLOBAL_METRICS.x``, ``self.metrics.x``) outside ``metrics.py``."""
    used = set()
    for path in sorted(PKG.rglob("*.py")):
        if path == PKG / "metrics.py":
            continue
        for node in ast.walk(_tree(path)):
            if isinstance(node, ast.Attribute) \
                    and "metrics" in ast.unparse(node.value).lower():
                used.add(node.attr)
    return used


@pytest.mark.parametrize("family", _metric_families())
def test_every_metric_family_has_a_writer(family):
    assert family in _metric_uses(), (
        f"Metrics.{family}: no file of volsync_tpu/ other than "
        f"metrics.py touches it; a family nothing sets goes")
