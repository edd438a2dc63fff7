import os
# 512 emulated host devices: this tool only lowers and compiles, and must
# never take an attached accelerator, so it pins JAX to the CPU.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

__doc__ = """Roofline baseline table: per (arch x shape) on the single-pod
mesh, derive the three roofline terms from scan-exact costing lowerings.

    PYTHONPATH=src python -m benchmarks.roofline_table [--arch A --shape S] [--out f.json]
"""

import argparse
import json
import traceback

from repro.sharding.rules import PerfOptions

from repro.configs import ARCH_IDS, INPUT_SHAPES
from repro.launch.mesh import make_production_mesh
from repro.launch.input_specs import skip_reason
from repro.configs import get_config, get_shape
from repro.roofline.analysis import format_table, make_row
from repro.roofline.costing import total_cost


def run(pairs, out=None, baseline=False):
    perf = PerfOptions.baseline() if baseline else PerfOptions()
    mesh = make_production_mesh(multi_pod=False)
    chips = 256
    rows, failures = [], []
    for arch_id, shape_id in pairs:
        if skip_reason(get_config(arch_id), get_shape(shape_id)):
            continue
        try:
            res = total_cost(arch_id, shape_id, mesh, dp_size=16, perf=perf)
            row = make_row(arch_id, shape_id, "16x16", chips, res["total"])
            rows.append(row)
            print(f"[ok] {arch_id} x {shape_id}: comp={row.compute_s*1e3:.3f}ms "
                  f"mem={row.memory_s*1e3:.3f}ms coll={row.collective_s*1e3:.3f}ms "
                  f"dom={row.dominant} useful={row.useful_ratio:.2f}")
        except Exception as e:
            traceback.print_exc()
            failures.append((arch_id, shape_id, str(e)))
    print()
    print(format_table(rows))
    if failures:
        print("\nFAILURES:")
        for f in failures:
            print(" ", f)
    if out:
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with open(out, "w") as f:
            json.dump([r.to_json() for r in rows], f, indent=1)
        print(f"wrote {out}")
    return rows, failures


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--out", default=None)
    ap.add_argument("--baseline", action="store_true",
                    help="use pre-hillclimb PerfOptions")
    args = ap.parse_args()
    if args.arch and args.shape:
        pairs = [(args.arch, args.shape)]
    else:
        pairs = [(a, s) for a in ARCH_IDS for s in INPUT_SHAPES]
    run(pairs, args.out, baseline=args.baseline)


if __name__ == "__main__":
    main()
