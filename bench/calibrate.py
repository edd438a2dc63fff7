#!/usr/bin/env python3
"""Readings that set a cell's correctness limit: the program's number on
many seeds and the control's (the reference in the next lower precision,
fp8) on the same, in one process so that set-up compiles once.

    python bench/calibrate.py --workload <cell> --seeds 1 2 3 ... --seconds 6 [--control 3]

Each seed builds the cell as a run does, serves a short window at the
cell's own load, frees the system and compares the same sample that a run
compares.  The first ``--control`` seeds also read the control on that
sample.  One JSON line per seed; the last line sums them up.  The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run as R


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, default=3)
    args = ap.parse_args()

    spec, cell, config, traffic = R.load_cell(args.workload)
    R.prepare_imports()
    devices, _ = R.chip_devices(cell["chips"])
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    driver = R.load_module(R.BENCH / "drivers" / f"{config['driver']}.py")
    reference = R.load_module(R.BENCH / "reference" / f"{config['reference']}.py")
    prog, ctrl = {}, {}
    for i, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        drv = driver.Driver(config, traffic, seed, reference, devices)
        drv.build()
        drv.window(args.seconds)
        n = drv.records()["attempted"]
        drv.release()
        got = drv.check()
        line = {"seed": seed, "attempted": n, "program": got}
        for k, v in got.items():
            prog.setdefault(k, []).append(v)
        if i < args.control:
            c = drv.check(control=True)
            line["control"] = c
            for k, v in c.items():
                ctrl.setdefault(k, []).append(v)
        line["seconds"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
        del drv
    print(json.dumps({"workload": cell["name"],
                      "program_max": {k: max(v) for k, v in prog.items()},
                      "control_min": {k: min(v) for k, v in ctrl.items()},
                      "program": prog, "control": ctrl}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
