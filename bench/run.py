#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` at the root of the
checkout: the cell's configuration (``bench/configs/<config>.json``), its
traffic mix (``bench/traffic/<traffic>.json``, whose ``kind`` names the
generator in ``bench/harness/<kind>.py``) and one reader per metric
(``bench/metrics/<metric>.py``).  With ``--trace 0`` the line carries the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics, read
from a profiler trace of the same window.

The run needs the cell's TPU chips: with none, or too few, it exits with
code 3 and prints no result.  Set-up (data, weights, warm-up, compilation)
is timed as ``setup_s``; nothing compiles in the window.  After the window
the outputs are checked against the plain reference, and each compared
number is printed with its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def load_cell(name: str) -> dict:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    config = next(c for c in spec["configs"] if c["name"] == cell["config"])
    model = json.loads((ROOT / config["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                         .read_text())

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    return {"cell": cell, "model": model, "traffic": traffic,
            "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
            "per_layer": [m for m in spec["per_layer"] if applies(m)]}


def reader(metric: str):
    """The metric's reader: ``bench/metrics/<metric>.py``, else, for a name
    split by kind (``device_idle_share.train``), the reader of its stem."""
    path = BENCH / "metrics" / f"{metric}.py"
    if not path.exists():
        path = BENCH / "metrics" / f"{metric.rsplit('.', 1)[0]}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_cell(args.workload)
    cell, model, traffic = spec["cell"], spec["model"], spec["traffic"]

    from bench.harness import device, output
    try:
        devs = device.require_tpu(cell["chips"])
    except device.NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 3
    device.enable_cache()
    meter = device.CompileMeter()
    kind = importlib.import_module(f"bench.harness.{traffic['kind']}")

    run = kind.Run(model["model"], {**traffic["params"],
                                    "seconds": args.seconds}, args.seed)
    run.warm()
    setup_s = time.perf_counter() - T_START
    lowered = meter.lowerings
    tdir = None
    if args.trace:
        import jax
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(tdir)
        with jax.profiler.TraceAnnotation("bench.window"):
            rec = run.window(args.seconds, annotate=True)
        jax.profiler.stop_trace()
    else:
        rec = run.window(args.seconds)
    rec["compiles_in_window"] = meter.lowerings - lowered
    print(json.dumps({"compiles_in_window": rec["compiles_in_window"],
                      "compile_s": meter.seconds,
                      "cache_requests": meter.cache_requests,
                      "cache_hits": meter.cache_hits}), file=sys.stderr,
          flush=True)
    dev = device.device_block(devs)
    run.free()
    breakdown = None
    if tdir:
        from bench.harness import trace
        rec["trace"] = trace.reduce(trace.find(tdir), window_s=rec["window_s"])
        shutil.rmtree(tdir, ignore_errors=True)
        dev["busy_s"] = rec["trace"]["busy_s"]
        dev["window_s"] = rec["window_s"]
        breakdown = rec["trace"]["breakdown"]
    checks = kind.check(run, traffic["limits"])

    rec.update(setup_s=setup_s, model=model["model"], chips=cell["chips"],
               device_kind=dev["kind"])
    metrics = {}
    for m in spec["per_layer" if args.trace else "end_to_end"]:
        value = reader(m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    output.emit(checks, rec["attempted"], rec["failed"], metrics, dev,
                breakdown)
    return 0


if __name__ == "__main__":
    sys.exit(main())
