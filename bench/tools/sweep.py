#!/usr/bin/env python3
"""Find the serving knee on the chip: offer a serving cell's traffic mix at
a ladder of Poisson rates, one window each, in one process.

    python3 bench/tools/sweep.py --workload serve.gru64.steady \
        --rates 20000,40000,60000 --seconds 8 --seed 1

For each rate it prints one JSON line: the rate offered, forecasts
completed per second, p50 and p99 latency, the generator's p99 lag, and
the backlog's growth (median latency of the last tenth of requests minus
that of the first tenth).  The knee is the highest rate whose p99 meets
the latency limit with no growing backlog; the cell's rate is set below it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="serve.gru64.steady")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import numpy as np
    from bench import run as bench_run
    from bench.harness import device, serve_open_loop
    spec = bench_run.load_cell(args.workload)
    device.require_tpu(spec["cell"]["chips"])
    device.enable_cache()
    model, traffic = spec["model"]["model"], spec["traffic"]["params"]
    for rate in (float(r) for r in args.rates.split(",")):
        run = serve_open_loop.Run(model, {**traffic, "rate": rate,
                                          "seconds": args.seconds},
                                  args.seed)
        run.warm()
        rec = run.window(args.seconds)
        lat = rec["latency_s"]
        k = max(len(lat) // 10, 1)
        print(json.dumps({
            "rate": rate, "served_per_s": rec["served"] / rec["window_s"],
            "p50_ms": float(np.percentile(lat, 50)) * 1e3,
            "p99_ms": float(np.percentile(lat, 99)) * 1e3,
            "lag_p99_ms": float(np.percentile(rec["lag_s"], 99)) * 1e3,
            "backlog_growth_ms": float(np.median(lat[-k:])
                                       - np.median(lat[:k])) * 1e3,
            "flush_ms": rec["busy_s"] / max(rec["flushes"], 1) * 1e3,
            "fill": rec["fill"], "window_s": rec["window_s"]}), flush=True)
        run.free()
    return 0


if __name__ == "__main__":
    sys.exit(main())
