"""Serving driver: a thin client of the ``repro.serving`` tier (the
micro-grid provider's deployment path, §5.4: the FL model is deployed to
1000s of unseen consumers with NO client-side retraining).

Trains a quick global (or per-cluster) model, publishes it into a
:class:`~repro.serving.ModelRegistry`, and replays unseen-consumer requests
through the padded-bucket :class:`~repro.serving.ServingEngine` — raw
watt-hours in, kWh forecasts out.  For throughput/latency numbers under a
Poisson request trace use ``benchmarks/bench_serving.py``.

  PYTHONPATH=src python -m repro.launch.serve --state CA --requests 256
  PYTHONPATH=src python -m repro.launch.serve --clusters 3 --int8
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FLConfig, ForecasterConfig
from repro.core import fedavg
from repro.data import synthetic
from repro.launch.compile_cache import enable_compile_cache
from repro.models import forecaster
from repro.serving import (ClusterRouter, ModelRegistry, ServingEngine,
                           bucket_for)


def serve_forecaster(params, cfg: ForecasterConfig, requests: np.ndarray,
                     batch: int = 1024):
    """requests: (n, lookback) NORMALIZED windows -> (n, horizon) forecasts.

    Batches are padded UP to the next power-of-two bucket and the pad rows
    sliced off, so the ragged final chunk (and any varying request count)
    reuses one of ≤ log2(batch)+1 compiled shapes instead of triggering a
    fresh XLA compile per distinct tail — regression-pinned via the
    jit-cache probe in ``tests/test_serving.py``.  Callers holding RAW
    watt-hour windows should use :class:`repro.serving.ServingEngine`,
    which also owns normalization and model hot-swap.
    """
    outs = []
    for i in range(0, len(requests), batch):
        chunk = np.asarray(requests[i:i + batch], np.float32)
        n = chunk.shape[0]
        b = bucket_for(n, 1, batch)
        if b > n:
            chunk = np.concatenate(
                [chunk, np.zeros((b - n,) + chunk.shape[1:], chunk.dtype)])
        x = jnp.asarray(chunk[..., None])
        outs.append(np.asarray(forecaster.forecast(params, x, cfg))[:n])
    return np.concatenate(outs)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--state", default="CA")
    ap.add_argument("--train-clients", type=int, default=24)
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--requests", type=int, default=256,
                    help="# of held-out consumers sending forecast requests")
    ap.add_argument("--days", type=int, default=120)
    ap.add_argument("--clusters", type=int, default=0,
                    help="k-means clusters (0 = single global model); "
                    "unseen consumers are routed by nearest centroid")
    ap.add_argument("--int8", action="store_true",
                    help="serve int8-quantized weights (4x smaller)")
    ap.add_argument("--max-batch", type=int, default=256)
    ap.add_argument("--min-bucket", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    fcfg = ForecasterConfig()
    flcfg = FLConfig(n_clients=args.train_clients,
                     clients_per_round=args.train_clients,
                     rounds=args.rounds, n_clusters=args.clusters,
                     seed=args.seed, lr=0.05,
                     cluster_days=min(273, int(args.days * 0.75)))
    print(f"[serve] quick FL fit on {args.train_clients} clients "
          f"({args.rounds} rounds, clusters={args.clusters or 'off'})")
    series = synthetic.generate_buildings(
        args.state, list(range(args.train_clients)), days=args.days)
    results = fedavg.run_federated_training(series, fcfg, flcfg)

    # ---- publish the trained globals into the serving registry
    registry = ModelRegistry()
    weights = "int8" if args.int8 else "fp32"
    qroot = jax.random.fold_in(jax.random.PRNGKey(args.seed), args.rounds)
    for cid, res in results.items():
        registry.publish(
            res.params, fcfg, slot=cid, generation=len(res.loss_history),
            weights=weights,
            key=jax.random.fold_in(qroot, cid + 1) if args.int8 else None)
    router = ClusterRouter.from_result(next(iter(results.values())))
    engine = ServingEngine(registry, router, max_batch=args.max_batch,
                           min_bucket=args.min_bucket)
    n_prog = engine.warmup()
    print(f"[serve] registry: slots {registry.slots()} ({weights}); "
          f"warmed {n_prog} bucket programs")

    # ---- replay raw watt-hour requests from unseen consumers
    print(f"[serve] serving {args.requests} unseen consumers")
    held = synthetic.generate_buildings(
        args.state, list(range(50_000, 50_000 + args.requests)),
        days=args.days)
    t0 = time.perf_counter()
    tickets = [engine.submit(50_000 + i, held[i, -fcfg.lookback:],
                             history=held[i])
               for i in range(args.requests)]
    engine.flush()
    dt = time.perf_counter() - t0
    assert all(t.done for t in tickets)
    st = engine.stats
    print(f"[serve] {args.requests} forecasts in {dt*1e3:.1f} ms "
          f"({dt/args.requests*1e6:.0f} µs/request, "
          f"{st.flushes} batches, fill {st.fill():.2f})")
    print(f"[serve] sample forecast (kWh, next hour): "
          f"{np.round(tickets[0].result, 2)}")


if __name__ == "__main__":
    main()
