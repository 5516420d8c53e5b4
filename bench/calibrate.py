"""Readings that set a cell's correctness limits, on the chip, at the
cell's own size; not part of a benchmark run.

    python3 bench/calibrate.py --workload <name> --seeds 11,12,13

For each seed, in one process: the cell's set-up and one unit of its
traffic (a fold cell: one round of silos_per_round updates; a client
cell: one update) with the program's own lower-precision path switched on (the f16 codec of
the plaintext partition), the comparison with the reference, and the
role's control readings (the reference in the next lower precision in the
program's place, and for training the fault of half of each batch left
out).  One JSON line per seed; the sound readings come from the
benchmark's own runs, which print every compared number.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import run as run_mod


def readings(cell, seed: int) -> dict:
    """One seed's control readings of `cell` (see the module docstring)."""
    import roles

    t0 = time.perf_counter()
    conf = dict(cell.config)
    conf["deployment"] = dict(conf["deployment"], plain_codec="f16")
    role = roles.ROLES[cell.traffic["role"]](
        dataclasses.replace(cell, config=conf), seed, roles.Spans(False))
    role.setup()
    role.unit()
    program_f16 = role.check()
    return {"workload": cell.name, "seed": seed,
            "program_f16_codec": program_f16, "control": role.control(),
            "seconds": time.perf_counter() - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(run_mod.ROOT, "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from repro.launch import compile_cache
    compile_cache.enable()

    import spec

    cell = spec.cell(args.workload, run_mod.ROOT)
    run_mod._device(cell.chips)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(cell, seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
