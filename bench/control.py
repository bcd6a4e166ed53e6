#!/usr/bin/env python3
"""The control of a cell's comparison: the plain reference computed one
precision below the configuration's, read as if it were the program.

    python bench/control.py --workload <cell> --seeds 1,2,3 [--jobs 2]

For each seed the cell's data is made as a run makes it, the reference is
computed at the configuration's ``dtype`` and again at the next precision
below (``LOWER``), and the lower one, put in the form of the program's
answer, is compared with the first by the cell's own comparison, for as
many jobs as a run compares.  One JSON line per seed.  A sound comparison
fails every such control by a wide margin; the numbers read here are the
upper readings the limits in ``bench/configs`` were set below.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

# The configuration's precision -> the nearest one below it.
LOWER = {"float32": "bfloat16", "int32": "int16"}


def control_numbers(cell, data, jobs: int) -> dict:
    """The worst of each compared number over ``jobs`` jobs, when the
    reference at the lower precision stands in for the program."""
    import jax.numpy as jnp

    mod, cfg, traffic = cell.job, cell.config, cell.traffic
    low = jnp.dtype(LOWER[cfg["dtype"]])
    worst: dict = {}
    for j in range(1, jobs + 1):
        want = mod.reference(data, cfg, traffic, j)
        got = mod.compare(
            mod.as_answer(mod.reference(data, cfg, traffic, j, dtype=low)), want
        )
        worst = {k: max(worst.get(k, 0), v) for k, v in got.items()}
    return worst


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--jobs", type=int, default=2,
                    help="jobs compared per seed (as many as a run compares)")
    args = ap.parse_args(argv)
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import run
    from repro.core import data_mesh

    cell = run.load_cell(args.workload)
    run.check_devices(cell.chips)
    run.enable_cache()
    mesh = data_mesh(cell.chips)
    for seed in (int(s) for s in args.seeds.split(",")):
        data = cell.job.generate(cell.config, cell.traffic, seed, mesh)
        nums = control_numbers(cell, data, args.jobs)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control": LOWER[cell.config["dtype"]],
                          "numbers": nums,
                          "limits": cell.config["limits"]}), flush=True)
        del data
    return 0


if __name__ == "__main__":
    sys.exit(main())
