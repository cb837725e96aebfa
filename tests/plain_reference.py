"""The benchmark's plain references, for the tier-1 tests that pin a model
family to one: ``benchmark/reference/<stem>.py`` loaded by path (the same
copy of the plain math decides ``correct`` on the chip), and a variables tree
as the flat ``{path: leaf}`` dict a reference reads."""

import importlib.util
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def load(stem: str):
    path = REPO / "benchmark" / "reference" / f"{stem}.py"
    spec = importlib.util.spec_from_file_location(f"bench_reference_{stem}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def flat_of(variables) -> dict:
    def walk(tree, prefix=""):
        for key, value in tree.items():
            path = f"{prefix}/{key}" if prefix else key
            if isinstance(value, dict):
                yield from walk(value, path)
            else:
                yield path, value
    return dict(walk(variables))
