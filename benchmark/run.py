"""The benchmark's one command:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process. It refuses anything but a TPU before doing work, resolves the
cell to its configuration and traffic files by name, hands the run to the
driver the configuration names, and prints ONE last line on standard output:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
``breakdown`` when traced), then the numbers compared beside their limits
under ``checks``. Everything else goes to standard error.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchlib import manifest, system  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--debug-dir", default=None,
                    help="builder's aid: keep a description and a 0.3 s excerpt of the trace here")
    return ap.parse_args(argv)


def judge(checks: dict) -> bool:
    """Every compared number against its own limit. A number that is not
    finite has failed."""
    ok = True
    for name, c in checks.items():
        value, limit = c["value"], c["limit"]
        if limit is None:
            continue
        if value != value:  # NaN
            ok = False
        elif c.get("sense", "max") == "max":
            ok = ok and value <= limit
        else:
            ok = ok and value >= limit
    return ok


def per_layer_metrics(wanted, reader_ctx) -> dict:
    """Each wanted per-layer metric through the reader its own file names. A
    reader that finds nothing to read returns None and the metric is left out."""
    specs = manifest.metric_files()
    readers = manifest.plugins("readers")
    out = {}
    for entry in wanted:
        spec = specs.get(entry["name"])
        if spec is None:
            system.say(f"no metrics/ file for {entry['name']!r}")
            continue
        reader = readers.get(spec["reader"])
        if reader is None:
            system.say(f"metric {entry['name']!r} names reader {spec['reader']!r}, which readers/ lacks")
            continue
        value = reader.read(reader_ctx, **spec.get("args", {}))
        if value is None:
            system.say(f"metric {entry['name']!r}: its reader found nothing to read")
            continue
        out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def traced(line, device, wanted, readings, cell, chip, debug_dir) -> None:
    """The traced run's part of the line: the per-layer metrics through their
    readers, the device's busy seconds, and the breakdown."""
    profile = readings["profile"]
    trace = profile.reduce()
    device["busy_s"] = trace.busy_s()
    device["window_s"] = trace.window_s
    reader_ctx = SimpleNamespace(
        **readings, trace=trace, peaks=chip, device=device, cell=cell,
        chips=int(cell["chips"]), kernels=manifest.plugins("kernels"))
    line["metrics"] = per_layer_metrics(wanted, reader_ctx)
    in_profile = [s for s in readings["spans"] if s["t1"] > trace.t0 and s["t0"] < trace.t1]
    line["breakdown"] = {
        "device_ops": [[n, s] for n, s in trace.top_ops(10)],
        "idle_gaps": [[n, s] for n, s in trace.idle_by_span(in_profile, 10)],
    }
    if debug_dir:
        from benchlib import trace as trace_lib

        os.makedirs(debug_dir, exist_ok=True)
        xplane = trace_lib.newest_xplane(profile.directory)
        Path(debug_dir, f"trace-{cell['name']}.txt").write_text(
            "\n".join(trace_lib.describe(xplane)))
        trace_lib.excerpt(xplane, str(Path(debug_dir, f"excerpt-{cell['name']}.json.gz")),
                          2.0e9, 2.3e9)


def main(argv=None) -> int:
    args = parse(argv)
    m = manifest.load()
    cell = manifest.cell(m, args.workload)
    config = manifest.config_of(m, cell)
    mix = manifest.traffic_of(cell)
    system.place_compile_cache()
    system.import_program()
    devices = system.require_tpu(int(cell["chips"]))
    import logging

    logging.basicConfig(level=logging.WARNING, stream=sys.stderr)
    ctx = system.run_context(m, cell, config, mix, devices, seed=args.seed, seconds=args.seconds,
                             trace=bool(args.trace), t_start=T_START,
                             compiles=system.CompileCounter())
    driver = manifest.plugin("drivers", config["driver"])
    result = driver.run(ctx)

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": result["memory_peak_bytes"]}
    line = {"correct": False, "attempted": result["attempted"], "failed": result["failed"]}
    wanted = manifest.wanted(m, cell["name"], bool(args.trace))
    if args.trace:
        traced(line, device, wanted, result["readings"], cell, ctx.peaks, args.debug_dir)
    else:
        line["metrics"] = {
            e["name"]: {"value": float(result["end_to_end"][e["name"]]), "unit": e["unit"]}
            for e in wanted}
    checks = result["checks"]
    line["correct"] = bool(judge(checks))
    line["device"] = device
    line["checks"] = {name: [c["value"], c["limit"]] for name, c in checks.items()
                      if c["limit"] is not None}
    for name, (value, limit) in line["checks"].items():
        print(f"check {name}: {value} (limit {limit})", file=sys.stderr)
    print(json.dumps(line), flush=True)
    shutil.rmtree(result["workdir"], ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
