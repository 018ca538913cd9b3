#!/usr/bin/env python3
"""Split a training cell's rounds on the chip by the program's own spans
and stage scopes: where each round's host time and device idle time go.

    python3 bench/tools/trace_round.py --workload train.lstm64.r1 --seed 1 \
        --seconds 20 [--untraced] [--python-tracer 0] [--set meters=16] \
        [--keep PATH]

Sets the cell up as ``bench/run.py`` does, runs one untraced window when
``--untraced`` is given (its samples/s is the base of the tracing cost),
then one window under the profiler inside a ``bench.window`` span, which
``bench/harness/trace.py`` and ``bench/harness/spans.py`` reduce.  Prints
three JSON lines: the windows' samples/s; the per-round numbers (each
layer's self time, the bytes put, the stages' device time, the existing
per-layer metrics); and the device idle seconds under each innermost
``fl.*`` span (``none``: under no span), beside the trace's idle seconds.
The profiler runs with its default options, as in ``bench/run.py``, which
trace every Python call; ``--python-tracer 0`` leaves that out (its cost
shows in the host spans).  The stage numbers need a round program compiled
with its scopes: the persistent compile cache's key leaves op metadata out,
so an entry written before the scopes existed serves without them (set
``JAX_COMPILATION_CACHE_DIR`` to an empty directory to compile afresh).
``--set key=value`` overrides a traffic parameter, for a small recorded
trace; ``--keep`` copies the trace file to PATH.
"""
from __future__ import annotations

import argparse
import importlib
import json
import shutil
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def _value(text: str):
    try:
        return json.loads(text)
    except json.JSONDecodeError:
        return text


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="train.lstm64.r1")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--untraced", action="store_true")
    ap.add_argument("--python-tracer", type=int, choices=(0, 1), default=1)
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=VALUE")
    ap.add_argument("--keep")
    args = ap.parse_args(argv)
    import jax

    from bench import run as bench_run
    from bench.harness import device, spans, trace
    spec = bench_run.load_cell(args.workload)
    device.require_tpu(spec["cell"]["chips"])
    device.enable_cache()
    params = dict(spec["traffic"]["params"], seconds=args.seconds)
    for kv in args.set:
        key, _, text = kv.partition("=")
        params[key] = _value(text)
    kind = importlib.import_module(
        f"bench.harness.{spec['traffic']['kind']}")
    run = kind.Run(spec["model"]["model"], params, args.seed)
    run.warm()
    rates = {}
    if args.untraced:
        rec = run.window(args.seconds)
        rates["untraced_samples_per_s"] = rec["samples"] / rec["window_s"]
    tdir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        options = jax.profiler.ProfileOptions()
        if not args.python_tracer:
            options.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=options)
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            rec = run.window(args.seconds, annotate=True)
        jax.profiler.stop_trace()
        path = trace.find(tdir)
        rec["trace"] = trace.reduce(path, window_s=rec["window_s"])
        red = spans.reduce(path, window_s=rec["window_s"])
        if args.keep:
            shutil.copyfile(path, args.keep)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    rates.update(traced_samples_per_s=rec["samples"] / rec["window_s"],
                 window_s=rec["window_s"], rounds=rec["rounds"])
    print(json.dumps(rates), flush=True)
    split = spans.per_round(red)
    for name in ("round_device_ms.train", "device_idle_share.train"):
        split[name] = bench_run.reader(name)(rec)
    print(json.dumps({"per_round": split, "spans": red["spans"],
                      "stages_s": red["stages"]}), flush=True)
    print(json.dumps({"idle_s": red["idle_s"],
                      "idle_s_by_span": red["idle_by_span"],
                      "idle_s_summed": sum(red["idle_by_span"].values())}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
