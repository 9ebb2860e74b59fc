#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

runs one cell once in this process: set-up (imports, the program built
from the cell's files, weights made on the device from the seed, every
program the cell's traffic can reach compiled or read from the cache,
the first steps that the check follows), the measured window, the
check against the plain reference, and one JSON line as the last line
of standard output. What a cell is, is data: ``BENCHMARK.json`` names
the cell, its configuration and its traffic, and the files of those
names under ``configs/``, ``workloads/`` and ``traffic/`` say the rest.
No cell's name appears in code.

It needs the accelerator the cell asks for: another platform than
``tpu``, fewer chips than the cell's ``chips`` or a device kind missing
from ``roofline/peaks.json`` is an error and prints no result.
``--rehearse`` is the builder's dry run (tiny sizes from the files'
``rehearsal`` sections, CPU backend): it prints counts, never a time,
rate or share.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)


def merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def load_cell(name: str, rehearse: bool) -> dict:
    from common import load_json
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next((w for w in manifest["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in manifest["configs"]
                if c["name"] == entry["config"])
    cell = {
        "name": name, "chips": entry["chips"], "manifest": manifest,
        "workload": load_json("workloads", name + ".json"),
        "config": json.load(open(os.path.join(ROOT, conf["file"]))),
        "traffic": load_json("traffic", entry["traffic"] + ".json"),
    }
    cell["config"]["name"] = conf["name"]
    cell["workload"]["chips"] = entry["chips"]
    if rehearse:
        for part in ("workload", "config", "traffic"):
            cell[part] = merge(cell[part], cell[part].get("rehearsal", {}))
    return cell


def metrics_of(cell: dict, section: str) -> list:
    """The manifest's metrics of ``section`` that this cell reports."""
    manifest, name = cell["manifest"], cell["name"]
    e2e_here = {m["name"] for m in manifest["end_to_end"]
                if name in m.get("workloads", [name])}
    out = []
    for m in manifest[section]:
        if "workloads" in m:
            if name in m["workloads"]:
                out.append(m)
        elif section == "end_to_end" or m["moves"] in e2e_here:
            out.append(m)
    return out


def read_layer_metric(name: str, ctx: dict):
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--keep-trace", default=None,
                    help="builder's option: copy the .xplane.pb here")
    args = ap.parse_args()

    cell = load_cell(args.workload, args.rehearse)
    wl = cell["workload"]
    # every program worth a cache entry: set-up is paid by every run
    os.environ.setdefault("PADDLE2_TPU_CACHE_MIN_COMPILE_S", "0")
    from drivers import program
    if not args.rehearse:
        program.apply_runtime_env(wl)

    import jax
    import common
    from common import CompileTally, device_record, load_module, note
    common.REHEARSAL = args.rehearse
    from roofline import peaks_for
    # set-up runs from the process's start; the accelerator runtime's
    # own start-up call (6-14 s on a one-chip machine, nobody's code
    # here) is inside it and also goes on an earlier line by itself
    t_pre = time.perf_counter()
    dev = device_record()
    backend_start_s = time.perf_counter() - t_pre
    want = "cpu" if args.rehearse else "tpu"
    if dev["platform"] != want or dev["count"] < cell["chips"]:
        print(f"run.py: cell {cell['name']} needs {cell['chips']} {want} "
              f"device(s); JAX reports {dev}", file=sys.stderr)
        return 2
    cell["peaks"] = None if args.rehearse else peaks_for(dev["kind"])
    cell["trace_dir"] = os.path.join(HERE, "_out", "trace", cell["name"])
    shutil.rmtree(cell["trace_dir"], ignore_errors=True)
    import paddle2_tpu  # noqa: F401  (settles the compile cache)
    from paddle2_tpu.flags import compile_cache_dir
    tally = CompileTally()
    note("start", device=dev, jax=jax.__version__, seed=args.seed,
         backend_start_s=backend_start_s,
         compile_cache_dir=compile_cache_dir(),
         libtpu_init_args=os.environ.get("LIBTPU_INIT_ARGS", ""))

    driver = load_module("drivers", wl["driver"])
    result = driver.run(cell, args, T_START, tally)

    values = dict(result["metrics"])
    values["setup_s"] = result["setup_s"]
    device = dict(dev, memory_peak_bytes=result["memory_peak_bytes"])
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"])}
    if args.trace:
        import trace_reduce
        xplane = trace_reduce.find_xplane(cell["trace_dir"])
        if args.keep_trace:
            os.makedirs(args.keep_trace, exist_ok=True)
            shutil.copy(xplane, args.keep_trace)
        trace = trace_reduce.load(xplane)
        ctx = dict(result["context"], trace=trace, cell=cell,
                   reduce=trace_reduce)
        busy_s, window_s = trace_reduce.busy_and_window_s(trace)
        if not args.rehearse:
            device.update(busy_s=busy_s, window_s=window_s)
        values = {}
        for m in metrics_of(cell, "per_layer"):
            v = read_layer_metric(m["name"], ctx)
            if v is not None:
                values[m["name"]] = v
        line["breakdown"] = {"device_ops": trace_reduce.top_ops(trace),
                             "idle_gaps": trace_reduce.idle_gaps(trace)}
        note("trace", **trace_reduce.summary(trace))
        shutil.rmtree(cell["trace_dir"], ignore_errors=True)
    units = {m["name"]: m["unit"] for m in metrics_of(
        cell, "per_layer" if args.trace else "end_to_end")}
    if args.rehearse:
        # a CPU run gives no time, rate or share: counts only
        line["metrics"] = {}
        line["rehearsal_metric_names"] = sorted(
            k for k in values if k in units)
    else:
        line["metrics"] = {k: {"value": float(v), "unit": units[k]}
                           for k, v in values.items() if k in units}
    line["device"] = device
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
