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


#: The model families behind the generation engine's seam: each imports its
#: shared blocks from ``models/blocks`` (and ``mamba2``, ``seeded_tree``,
#: ``parallel/moe``), never from another family.
FAMILIES = ("nemotron_h", "olmo_hybrid", "lfm2_moe", "deepseek_v3", "granite_hybrid")


def imported_modules(source: Path) -> set[str]:
    """Every ``dmlc_tpu`` module ``source`` imports, dotted, inside functions too."""
    found: set[str] = set()
    for node in ast.walk(ast.parse(source.read_text())):
        if isinstance(node, ast.Import):
            modules = [alias.name for alias in node.names]
        elif isinstance(node, ast.ImportFrom):
            assert node.level == 0, f"{source}: relative import"
            modules = [node.module or ""]
            if node.module and node.module.split(".")[0] == "dmlc_tpu":
                # ``from dmlc_tpu.models import registry`` imports the module too
                modules += [f"{node.module}.{alias.name}" for alias in node.names]
        else:
            continue
        found.update(m for m in modules if m.split(".")[0] == "dmlc_tpu")
    return found


def imported_packages(package: str) -> set[str]:
    found: set[str] = set()
    for source in (PACKAGE / package).rglob("*.py"):
        for module in imported_modules(source):
            parts = module.split(".")
            if len(parts) > 1:
                found.add(parts[1])
    return found - {package}


@pytest.mark.parametrize("package", sorted(IMPORTS))
def test_package_imports_no_more_than_recorded(package):
    new = imported_packages(package) - IMPORTS[package]
    assert not new, (
        f"dmlc_tpu/{package} now imports {sorted(new)}: a new edge in the "
        "package graph (ROADMAP D17); move the shared piece down instead"
    )


def test_no_model_family_imports_another():
    for family in FAMILIES:
        imported = imported_modules(PACKAGE / "models" / f"{family}.py")
        others = {f"dmlc_tpu.models.{f}" for f in FAMILIES if f != family}
        assert not imported & others, (
            f"dmlc_tpu/models/{family}.py imports {sorted(imported & others)}: a block more "
            "than one family uses belongs in models/blocks (ROADMAP D22)"
        )


def test_every_package_is_recorded():
    on_disk = {p.parent.name for p in PACKAGE.glob("*/__init__.py")}
    assert on_disk == set(IMPORTS)
