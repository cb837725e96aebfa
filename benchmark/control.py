"""The control of ``correct``, run by a builder on the chip and never by the
benchmark's own runs:

    python benchmark/control.py --workload <name> --seeds 1,2,3 --seconds 12 \
        [--precision fp8] [--out chiprun_out/control.jsonl]

For each seed, in ONE process (set-up is most of a run): a short window at the
cell's own load through the same driver, the numbers the run compares (the
program's reading), and the same numbers for the reference computed in the
nearest precision below the configuration's over the same sample (the
control's reading). Both go through the comparison that decides ``correct``
(``run.judge``) against the configuration's limits: every row carries
``program_correct`` and ``control_correct``, and the command exits 1 unless
the program came out correct and the control not correct on every seed. A
limit belongs between the largest of the first and the smallest of the second.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchlib import manifest, system  # noqa: E402
from benchlib.lowprec import BELOW  # noqa: E402
from run import judge  # noqa: E402

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--precision", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    m = manifest.load()
    cell = manifest.cell(m, args.workload)
    config = manifest.config_of(m, cell)
    mix = manifest.traffic_of(cell)
    system.place_compile_cache()
    system.import_program()
    devices = system.require_tpu(int(cell["chips"]))
    import logging

    logging.basicConfig(level=logging.WARNING, stream=sys.stderr)
    precision = args.precision or BELOW[config["dtype"]]
    driver = manifest.plugin("drivers", config["driver"])
    compiles = system.CompileCounter()
    t0 = T_START
    as_it_should = True
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = system.run_context(m, cell, config, mix, devices, seed=seed, seconds=args.seconds,
                                 trace=False, t_start=t0, compiles=compiles, control=precision)
        result = driver.run(ctx)
        shutil.rmtree(result["workdir"], ignore_errors=True)
        row = {"workload": cell["name"], "seed": seed, "precision": precision,
               "failed": result["failed"], "attempted": result["attempted"],
               "end_to_end": result["end_to_end"],
               "program": {k: v["value"] for k, v in result["checks"].items()},
               "control": {k: v["value"] for k, v in result["control_checks"].items()},
               "limits": {k: v["limit"] for k, v in result["checks"].items()
                          if v["limit"] is not None},
               "program_correct": bool(judge(result["checks"])),
               "control_correct": bool(judge(result["control_checks"]))}
        as_it_should = as_it_should and row["program_correct"] and not row["control_correct"]
        print(json.dumps(row), flush=True)
        if args.out:
            Path(args.out).parent.mkdir(parents=True, exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
        t0 = time.perf_counter()
    print(f"control: program correct and control not correct on every seed: {as_it_should}",
          file=sys.stderr)
    return 0 if as_it_should else 1


if __name__ == "__main__":
    sys.exit(main())
