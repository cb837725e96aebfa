"""Import-direction ratchet: which packages of ``dmlc_tpu`` each package imports.

The layers, bottom up, are meant to be utils, native, ops, models, parallel,
generate, scheduler, cluster. Today some arrows point up (ROADMAP D17):
``cluster/`` holds both the composition root (``node.py``, ``localcluster.py``)
and things everything else needs (``tracectx``, ``devicemon.CensusedJit``,
``rpc``, ``deadline``, ``tenant``), and ``models`` and ``parallel`` import each
other. The table records what each package imports as of PR 29, read from the
sources with ``ast`` (imports inside functions count). It may only SHRINK: a
new edge fails here; when an edge goes away, take it out of the table.
"""

import ast
from pathlib import Path

import pytest

PACKAGE = Path(__file__).resolve().parent.parent / "dmlc_tpu"

#: package -> packages it may import. Upward edges are marked.
IMPORTS = {
    "utils": {"cluster"},  # up: tracing -> cluster/tracectx
    "native": {"utils"},
    "ops": {"utils", "native",
            "parallel", "cluster"},  # up: ring_attention; devicemon.CensusedJit
    "models": {"parallel"},  # up: sharding, sp_transformer, moe
    "parallel": {"utils", "ops", "models",
                 "cluster"},  # up: devicemon (census), rpc (multihost)
    "generate": {"utils", "ops", "models",
                 "cluster"},  # up: devicemon, deadline, tenant, tracectx, rpc
    "scheduler": {"utils", "ops", "models", "parallel",
                  "cluster"},  # up: rpc, tenant, deadline, tracectx
    "cluster": {"utils", "native", "ops", "models", "parallel", "generate",
                "scheduler"},  # the composition root imports everything
}


def imported_packages(package: str) -> set[str]:
    found: set[str] = set()
    for source in (PACKAGE / package).rglob("*.py"):
        for node in ast.walk(ast.parse(source.read_text())):
            if isinstance(node, ast.Import):
                modules = [alias.name for alias in node.names]
            elif isinstance(node, ast.ImportFrom):
                assert node.level == 0, f"{source}: relative import"
                modules = [node.module or ""]
                if node.module == "dmlc_tpu":
                    modules = [f"dmlc_tpu.{alias.name}" for alias in node.names]
            else:
                continue
            for module in modules:
                parts = module.split(".")
                if parts[0] == "dmlc_tpu" and len(parts) > 1:
                    found.add(parts[1])
    return found - {package}


@pytest.mark.parametrize("package", sorted(IMPORTS))
def test_package_imports_no_more_than_recorded(package):
    new = imported_packages(package) - IMPORTS[package]
    assert not new, (
        f"dmlc_tpu/{package} now imports {sorted(new)}: a new edge in the "
        "package graph (ROADMAP D17); move the shared piece down instead"
    )


def test_every_package_is_recorded():
    on_disk = {p.parent.name for p in PACKAGE.glob("*/__init__.py")}
    assert on_disk == set(IMPORTS)
