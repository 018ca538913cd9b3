#!/usr/bin/env python3
"""Read the compared numbers of a cell over many seeds on the chip: the
program's own, the control's (the reference in bfloat16 put in the
program's place) and, for training, each planted fault's.  The limits in
``bench/traffic/<traffic>.json`` are set from these readings.

    python3 bench/tools/calibrate.py --workload train.lstm64.r1 \
        --seeds 101,102,103 --variants program,control,half_batch

Training reads its numbers from set-up alone (one round, then one whole
call); serving runs one window of ``--seconds`` per seed at the cell's own
load.  One JSON line per seed and variant.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="program,control")
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    from bench import run as bench_run
    from bench.harness import device
    spec = bench_run.load_cell(args.workload)
    device.require_tpu(spec["cell"]["chips"])
    device.enable_cache()
    traffic = spec["traffic"]
    kind = importlib.import_module(f"bench.harness.{traffic['kind']}")
    for seed in (int(s) for s in args.seeds.split(",")):
        run = kind.Run(spec["model"]["model"],
                       {**traffic["params"], "seconds": args.seconds}, seed)
        run.warm()
        if traffic["kind"] != "fl_round":
            run.window(args.seconds)
        run.free()
        for v in args.variants.split(","):
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "variant": v, **kind.readings(run, v)}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
