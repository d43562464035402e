"""Static checks of the stack's layering and of where a call's dispatch
path is chosen.

* The numeric layers (``repro.blas``, ``repro.lapack``, ``repro.tune``)
  sit under the ``repro.linalg`` front end and never import it.
* The policy, registry and mesh of a call come from the
  ``repro.linalg.ExecutionContext`` alone, and interpret mode from the
  device inside the kernels: no function from the front end down to the
  dispatcher takes a boolean ``use_*`` kernel switch (``use_pallas`` and
  its like) or an ``interpret`` flag.

Both are AST scans of the source, so no module is imported or traced.
"""
import ast
import dataclasses
import pathlib

import pytest

SRC = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"


def _modules(*packages):
    return sorted(p.relative_to(SRC).as_posix()
                  for pkg in packages for p in (SRC / pkg).glob("*.py"))


def _imported_modules(tree):
    """Every module name an import statement of ``tree`` names, nested
    (function-local) imports included."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name
        elif isinstance(node, ast.ImportFrom) and node.module:
            yield node.module
            for alias in node.names:
                yield f"{node.module}.{alias.name}"


@pytest.mark.parametrize("package", ["blas", "lapack", "tune"])
def test_numeric_layers_do_not_import_the_front_end(package):
    upward = [(mod, name) for mod in _modules(package)
              for name in _imported_modules(ast.parse((SRC / mod).read_text()))
              if name == "repro.linalg" or name.startswith("repro.linalg.")]
    assert upward == [], f"repro.{package} imports the front end: {upward}"


@pytest.mark.parametrize("module",
                         _modules("blas", "lapack", "linalg", "tune"))
def test_no_dispatch_switch_above_the_kernels(module):
    tree = ast.parse((SRC / module).read_text())
    found = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            args = node.args
            for arg in args.posonlyargs + args.args + args.kwonlyargs:
                if arg.arg == "interpret" or arg.arg.startswith("use_"):
                    found.append((getattr(node, "name", "<lambda>"),
                                  arg.arg))
    assert found == [], f"{module}: dispatch switches {found}"


def test_execution_context_has_no_interpret_field():
    from repro import linalg
    fields = {f.name for f in dataclasses.fields(linalg.ExecutionContext)}
    assert "interpret" not in fields
    assert fields == {"policy", "mesh", "registry", "accum_dtype", "machine",
                      "obs"}
