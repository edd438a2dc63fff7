#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is found by name from ``BENCHMARK.json``: its
configuration file (which names its driver under ``bench/drivers/`` and
its plain reference under ``bench/reference/``), its traffic file
``bench/traffic/<traffic>.json``, and one reader per metric,
``bench/metrics/<metric>.py``.  Adding a cell or a metric adds files and
edits none.

A run builds the system from the seed and warms it up (set-up), serves the
traffic for ``--seconds`` (the window), reads the peak device memory, frees
the system, and compares a seeded sample of what the window produced with
the reference.  With ``--trace 0`` it reports the cell's end-to-end
metrics; with ``--trace 1`` it records a profiler trace of the window and
reports the per-layer metrics.  It exits non-zero, printing no result,
where JAX finds no TPU, fewer chips than the cell asks for, or a device
kind that ``bench/peaks.py`` does not list.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_module(path: Path):
    """Import a file by path; its name may hold '.' and '-'."""
    name = "bench_" + str(path.relative_to(BENCH)).replace("/", "_").replace(
        ".", "_").replace("-", "_")
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def load_cell(name: str):
    """(benchmark spec, cell entry, configuration, traffic) for a cell."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"run.py: no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = json.loads((ROOT / entry["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    return spec, cell, config, traffic


def cell_metrics(spec: dict, cell: dict, trace: bool) -> list[dict]:
    """The metrics this cell reports: end-to-end without a trace, per-layer
    with one.  A metric without a ``workloads`` list goes to every cell
    (per-layer: every cell that reports the end-to-end metric it moves)."""
    e2e = [m for m in spec["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (cell["name"] in m["workloads"] if "workloads" in m else m["moves"] in names)]


def prepare_imports() -> None:
    """Make the program importable from the checkout, and keep JAX's
    compilation cache and the program's derived dispatch tables at fixed
    paths inside the checkout, so that a checkout's first run of a cell
    compiles and later runs find everything.  Call before JAX is imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["REPRO_DISPATCH_CACHE"] = str(ROOT / ".dispatch_cache")
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))


def chip_devices(chips: int):
    """The first ``chips`` TPU devices and their peaks; exits where there
    are none, too few, or their kind has no peaks."""
    import jax

    peaks = load_module(BENCH / "peaks.py")
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"run.py: needs a TPU; JAX found platform {devices[0].platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"run.py: the cell needs {chips} chips; JAX found {len(devices)}")
    try:
        p = peaks.peaks_for(devices[0].device_kind)
    except KeyError as e:
        raise SystemExit(f"run.py: {e}")
    return devices[:chips], p


def _trace_view(path: str, devices) -> SimpleNamespace:
    tr = load_module(BENCH / "trace_reduce.py")
    t = tr.load(path)
    lo, hi = t.host_window("bench.window")
    devs = [d.id for d in devices if d.id in t.ops] or sorted(t.ops)[:len(devices)]
    busy = [tr.busy_ns(t, d, lo, hi) / 1e9 for d in devs]
    ops = tr.op_seconds(t, devs[:1], lo, hi)
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    return SimpleNamespace(
        trace=t, lo=lo, hi=hi, devs=devs, reduce=tr,
        busy_s=sum(busy) / len(busy), window_s=(hi - lo) / 1e9,
        breakdown={"device_ops": [[k, v] for k, v in top],
                   "idle_gaps": tr.idle_by_host(t, devs[0], lo, hi,
                                                skip=("bench.window",))})


def run_cell(spec, cell, config, traffic, *, seed: int, seconds: float, trace: bool,
             devices, peaks: dict | None, t_start: float) -> dict:
    """Set up, serve the window, check, and return the result object."""
    import jax

    from repro.launch.compile_cache import compile_stats, enable_compile_cache

    enable_compile_cache()
    driver = load_module(BENCH / "drivers" / f"{config['driver']}.py")
    reference = load_module(BENCH / "reference" / f"{config['reference']}.py")
    drv = driver.Driver(config, traffic, seed, reference, devices)
    drv.build()
    cs0 = compile_stats()
    tmp = tempfile.TemporaryDirectory() if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # runtime and benchmark spans only
        jax.profiler.start_trace(tmp.name, profiler_options=opts)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        drv.window(seconds)
    t1 = time.perf_counter()
    view = None
    if trace:
        jax.profiler.stop_trace()
        view = _trace_view(glob.glob(f"{tmp.name}/**/*.xplane.pb", recursive=True)[0],
                           devices)
        tmp.cleanup()
    cs1 = compile_stats()
    stats = [d.memory_stats() or {} for d in devices]
    mem_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    rec = drv.records()
    drv.release()
    checks = drv.check()
    limits = config["limits"]
    run = SimpleNamespace(cell=cell, config=config, traffic=traffic, peaks=peaks,
                          setup_s=t0 - t_start, window_s=t1 - t0, chips=len(devices),
                          view=view, flops=load_module(BENCH / "flops.py"),
                          load=lambda rel: load_module(BENCH / rel), **rec)
    metrics = {}
    for m in cell_metrics(spec, cell, trace):
        value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    compared = {k: {"value": checks[k], "limit": lim} for k, lim in limits.items()}
    failed = rec.get("failed", 0)
    correct = (failed == 0 and rec["attempted"] > 0
               and all(v["limit"] is not None and v["value"] <= v["limit"]
                       for v in compared.values()))
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": int(mem_peak)}
    if view is not None:
        device.update(busy_s=view.busy_s, window_s=view.window_s)
    out = {"correct": bool(correct), "attempted": rec["attempted"], "failed": failed,
           "metrics": metrics, "device": device}
    if view is not None:
        out["breakdown"] = view.breakdown
    out["checks"] = compared
    print(f"[run] set-up {run.setup_s:.3f} s, window {run.window_s:.3f} s; compilations: "
          f"{cs0['compiles']} in set-up ({cs0['compile_s']:.3f} s, {cs0['cache_hits']} "
          f"cache hits), {cs1['compiles'] - cs0['compiles']} in the window", file=sys.stderr)
    for k, v in compared.items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})", file=sys.stderr)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec, cell, config, traffic = load_cell(args.workload)
    prepare_imports()
    devices, peaks = chip_devices(cell["chips"])
    try:
        out = run_cell(spec, cell, config, traffic, seed=args.seed, seconds=args.seconds,
                       trace=bool(args.trace), devices=devices, peaks=peaks,
                       t_start=T_START)
    except Exception:
        traceback.print_exc()
        return 1
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
