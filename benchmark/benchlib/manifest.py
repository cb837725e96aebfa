"""BENCHMARK.json and the files it names, found by name and by globbing.

No list in any Python file names a cell, a metric, a reader, a kernel, a
driver or a reference: a later PR adds files and manifest entries.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent


def load(repo: Path = REPO) -> dict:
    return json.loads((repo / "BENCHMARK.json").read_text())


def read_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json; "
                     f"have {[w['name'] for w in manifest['workloads']]}")


def config_of(manifest: dict, workload: dict, repo: Path = REPO) -> dict:
    """The configuration's file, as the manifest's ``configs`` entry names it."""
    for c in manifest["configs"]:
        if c["name"] == workload["config"]:
            return read_json(repo / c["file"])
    raise SystemExit(f"benchmark: workload {workload['name']!r} names config "
                     f"{workload['config']!r}, which BENCHMARK.json does not list")


def traffic_of(workload: dict, bench: Path = BENCH) -> dict:
    return read_json(bench / "traffic" / f"{workload['traffic']}.json")


def limits_of(config: dict, traffic_name: str) -> dict:
    """The limit of each number ``correct`` compares: the configuration's
    ``default`` table, overridden by one named after the traffic mix."""
    table = config.get("limits", {})
    return {**table.get("default", {}), **table.get(traffic_name, {})}


def metric_files(bench: Path = BENCH) -> dict[str, dict]:
    """Every per-layer metric's own file, keyed by the metric's name."""
    out = {}
    for path in sorted((bench / "metrics").glob("*.json")):
        spec = read_json(path)
        out[spec["name"]] = spec
    return out


def plugins(kind: str, bench: Path = BENCH) -> dict[str, object]:
    """Modules globbed from ``benchmark/<kind>/*.py`` (readers, kernels,
    drivers, reference), keyed by file stem."""
    out = {}
    for path in sorted((bench / kind).glob("*.py")):
        if path.stem.startswith("_"):
            continue
        out[path.stem] = load_module(f"bench_{kind}_{path.stem}", path)
    return out


def plugin(kind: str, stem: str, bench: Path = BENCH):
    path = bench / kind / f"{stem}.py"
    if not path.exists():
        raise SystemExit(f"benchmark: no {kind} file {path}")
    return load_module(f"bench_{kind}_{stem}", path)


def load_module(name: str, path: Path):
    if name in sys.modules and getattr(sys.modules[name], "__file__", None) == str(path):
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def reports(metric: dict, cell_name: str) -> bool:
    """Does ``metric`` (a manifest entry) exist in ``cell_name``? Without a
    ``workloads`` key it exists everywhere."""
    return "workloads" not in metric or cell_name in metric["workloads"]


def wanted(manifest: dict, cell_name: str, trace: bool) -> list[dict]:
    """The manifest entries this run's result line carries: the cell's
    end-to-end metrics untraced, its per-layer metrics traced."""
    section = manifest["per_layer"] if trace else manifest["end_to_end"]
    return [m for m in section if reports(m, cell_name)]
