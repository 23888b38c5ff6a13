"""Which package may import which, read from the source with `ast`.

Nothing of `cobrix_tpu` is imported here, so a layering fault shows in
milliseconds and names its file and line. An import inside a function
counts like one at the top of a module: a lazy arrow still points up.

    copybook, encoding, plan, ops      the decode plane's own layer
    reader, native, engine, parallel   executors over it
    api, serve, fleet, streaming,      front doors
    sink, bridge, explain

Arrows that point up and are NOT yet forbidden (ROADMAP C15). Forbidding
one here is how that debt is paid:

    io/* -> reader.stream, reader.index   (the byte-source interface
                                           belongs in io)
    io/peercache.py -> serve.protocol
    native/__init__.py -> reader.diagnostics
    utils/file_utils.py, utils/flatten.py -> api
    reader/fixed_len_reader.py, reader/var_len_reader.py
        -> query.pushdown (which imports api)
    encoding/codepages.py -> plan.cache
    copybook/copybook.py -> ops.scalar_decoders
"""
import ast
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = "cobrix_tpu"

DECODE_PLANE = ("copybook", "encoding", "plan", "ops")
BELOW_DECODE_PLANE = set(DECODE_PLANE) | {"profiling"}
EXECUTORS = ("reader", "native", "engine", "parallel")
FRONT_DOORS = {"api", "serve", "fleet", "streaming", "sink", "bridge",
               "explain"}


def _harnesses():
    """Top-level names of everything beside the package: the scripts and
    directories at the root of the checkout (`chip_smoke`, `benchmark`,
    `tools`, ...) and the scripts under `tools/`, which their tests import
    by bare name. They import the package, never the other way round
    (`cobrix_tpu.tools` is the package's own)."""
    beside = {os.path.splitext(name)[0] for name in os.listdir(REPO)
              if not name.startswith(".")} - {PACKAGE}
    scripts = {os.path.splitext(name)[0]
               for name in os.listdir(os.path.join(REPO, "tools"))}
    return beside | scripts


def _modules(subpackage=None):
    """(path, dotted package the module lives in) of every .py file."""
    top = os.path.join(REPO, PACKAGE, subpackage or "")
    for folder, _dirs, files in os.walk(top):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(folder, name)
                inside = os.path.relpath(folder, REPO).split(os.sep)
                yield path, inside


def _imports(path, inside):
    """(absolute dotted name, line) of everything the module imports.
    `from pkg import a` gives `pkg.a` where `pkg` is the package root or
    a bare relative level, since `a` may itself be a subpackage."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name, node.lineno
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                base = inside[:len(inside) - (node.level - 1)]
                dotted = ".".join(base + ([node.module]
                                          if node.module else []))
            else:
                dotted = node.module
            if dotted == PACKAGE or (node.level and not node.module):
                for alias in node.names:
                    yield f"{dotted}.{alias.name}", node.lineno
            else:
                yield dotted, node.lineno


def _arrows(subpackage):
    """(subpackage of cobrix_tpu imported, where) for one subpackage's
    imports of the rest of the package."""
    for path, inside in _modules(subpackage):
        for dotted, line in _imports(path, inside):
            parts = dotted.split(".")
            if parts[0] == PACKAGE and len(parts) > 1 \
                    and parts[1] != subpackage:
                yield parts[1], f"{os.path.relpath(path, REPO)}:{line}"


@pytest.mark.parametrize("subpackage", DECODE_PLANE)
def test_decode_plane_imports_only_its_own_layer(subpackage):
    up = [(to, where) for to, where in _arrows(subpackage)
          if to not in BELOW_DECODE_PLANE]
    assert not up, up


@pytest.mark.parametrize("subpackage", EXECUTORS)
def test_executors_import_no_front_door(subpackage):
    up = [(to, where) for to, where in _arrows(subpackage)
          if to in FRONT_DOORS]
    assert not up, up


def test_package_imports_no_harness():
    harnesses = _harnesses()
    assert {"chip_smoke", "benchmark", "tools", "asmcheck"} <= harnesses
    up = [(dotted, f"{os.path.relpath(path, REPO)}:{line}")
          for path, inside in _modules()
          for dotted, line in _imports(path, inside)
          if dotted.split(".")[0] in harnesses]
    assert not up, up


def test_readme_names_only_files_that_exist():
    """Every path README.md writes with a directory prefix, and every
    script it runs as `python X.py`, is in the tree. A bare module name
    inside a package's own paragraph is not a path and is not checked."""
    with open(os.path.join(REPO, "README.md"), encoding="utf-8") as f:
        text = f.read()
    prefixed = re.findall(
        r"(?<![\w/.-])"
        r"((?:cobrix_tpu|tools|benchmark|tests|examples)/[\w./-]+)", text)
    run = re.findall(r"\bpython3? +([\w./-]+\.py)\b", text)
    named = {p.rstrip(".,") for p in prefixed} | set(run)
    assert len(named) > 20  # the patterns still find the README's paths
    missing = sorted(p for p in named
                     if not os.path.exists(os.path.join(REPO, p)))
    assert not missing, missing
