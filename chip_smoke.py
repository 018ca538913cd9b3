#!/usr/bin/env python3
"""Smoke run of the main path on a TPU: train, reference, serve.

Drives the paper's forecaster (LSTM, H = 64, look-back 8, horizon 4) at full
width through the calls a user makes — ``fedavg.run_federated_training``,
``evaluate_unseen_clients``, the local update (its LSTM differentiated by
the fused Pallas kernels), ``ModelRegistry`` and ``ServingEngine`` — and
checks every result against a reference.  Each phase prints one JSON line
with its compile seconds and its steady-state seconds apart; the last line
is ``{"ok": true, "device": {...}}``.  A phase that fails raises, and the
script exits non-zero without that line.  With no TPU it stops at once:
nothing runs on the CPU in its place.

Every phase runs in this one process, which holds the chip.

  python chip_smoke.py               # one chip: train, reference, serve
  python chip_smoke.py --four-chips  # four chips: mesh rounds vs one chip
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.analysis import recompile  # noqa: E402
from repro.configs.base import FLConfig, ForecasterConfig  # noqa: E402
from repro.core import aggregation, client, fedavg, losses  # noqa: E402
from repro.data import partition, synthetic, windows  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import forecaster  # noqa: E402
from repro.serving import ModelRegistry, ServingEngine  # noqa: E402

# The chip runs f32 matmuls at XLA's DEFAULT precision: one bf16 pass, whose
# rounded inputs carry a unit roundoff u = 2^-8 ≈ 3.9e-3.  Emulating that
# rounding on the host moves the first local update's delta by ~2e-3
# (max-normalised), so a chip result is held to 5u against an f32 one.
TOL_BF16 = 2e-2
# A mesh round runs the one-chip round's per-client program on each chip;
# only the f32 order of the cross-client sum changes (~1e-6 relative).  The
# bound leaves the 410 local SGD steps room to amplify that, and stays u/4.
TOL_MESH = 1e-3
INT8_MAPE_BOUND = 0.02          # the fp32-vs-int8 pin of tests/test_serving.py

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class SmokeFailure(RuntimeError):
    """A check of the smoke run did not hold."""


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Workload of one smoke run; the defaults are the real sizes."""
    meters: int = 1024           # synthetic CA training meters
    days: int = 365              # a year of 15-minute history each
    clients_per_round: int = 256
    rounds: int = 3
    heldout: int = 64            # unseen meters for evaluate_unseen_clients
    consumers: int = 1024        # unseen consumers sending serving requests
    max_batch: int = 256         # largest serving bucket
    ref_clients: int = 4         # slice checked against the host CPU
    ref_steps: int = 8
    mesh_meters: int = 512       # --four-chips population
    dp_clients: int = 64         # ring-masked int8 round (b = 8 ring bound)


class CompileMeter:
    """Seconds JAX spent lowering and compiling (or loading from the
    persistent cache), and the cache's hits, from its monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_requests = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.cache_requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _emit(phase: str, compile_s: float, steady_s: float, **fields) -> None:
    print(json.dumps({"phase": phase, "ok": True, "compile_s": compile_s,
                      "steady_s": steady_s, **fields}), flush=True)


def _rel(a, b) -> float:
    """Largest difference over the leaves of two trees, each leaf's
    normalised by the reference leaf's largest magnitude."""
    out = 0.0
    for u, v in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        u, v = np.asarray(u, np.float64), np.asarray(v, np.float64)
        out = max(out, float(np.max(np.abs(u - v))
                             / max(float(np.max(np.abs(v))), 1e-30)))
    return out


def _row_rel(a: np.ndarray, b: np.ndarray) -> float:
    """Largest per-row max-normalised difference of two (n, H) forecasts."""
    return float(np.max(np.max(np.abs(a - b), axis=1)
                        / np.maximum(np.max(np.abs(b), axis=1), 1e-30)))


def _delta(local, global_):
    return jax.tree.map(lambda l, g: l - g, local, global_)


def _on_tpu(tree) -> bool:
    return all(isinstance(x, jax.Array)
               and all(d.platform == "tpu" for d in x.devices())
               for x in jax.tree.leaves(tree))


@contextlib.contextmanager
def _scan_recurrence():
    """Trace the LSTM's recurrence as the jnp scan while inside.  The fused
    kernels are chosen from the default backend, the chip's, when traced;
    the host CPU backend cannot run them."""
    chosen = forecaster.fused_recurrence
    forecaster.fused_recurrence = lambda cfg: False
    jax.clear_caches()
    try:
        yield
    finally:
        forecaster.fused_recurrence = chosen
        jax.clear_caches()


@functools.partial(jax.jit, static_argnames=("cfg", "loss"))
def _local_updates(params, x, y, bidx, lr, cfg, loss):
    """The local-update stage of the round, vmapped over clients."""
    one = functools.partial(client.local_update, cfg=cfg, loss=loss)
    return jax.vmap(one, in_axes=(None, 0, 0, 0, None))(params, x, y, bidx,
                                                        lr)


def _flcfg(sizes: Sizes, seed: int, **kw) -> FLConfig:
    base = dict(n_clients=sizes.meters,
                clients_per_round=sizes.clients_per_round, rounds=sizes.rounds,
                local_epochs=1, batch_size=64, lr=0.05, loss="ew_mse",
                beta=2.0, n_clusters=0, seed=seed)
    return FLConfig(**{**base, **kw})


# ------------------------------------------------------------------ phases
def phase_device(cache_dir: str) -> jax.Device:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SmokeFailure(
            f"JAX's default platform is {dev.platform!r}, not 'tpu': this "
            "smoke run measures the chip and never falls back to another "
            "backend")
    print(json.dumps({"phase": "device", "platform": dev.platform,
                      "kind": dev.device_kind, "count": len(jax.devices()),
                      "jax": jax.__version__, "compile_cache": cache_dir}),
          flush=True)
    return dev


def phase_train(meter: CompileMeter, sizes: Sizes, seed: int,
                fcfg: ForecasterConfig):
    series = synthetic.generate_buildings("CA", list(range(sizes.meters)),
                                          days=sizes.days)
    flcfg = _flcfg(sizes, seed)
    c0, t0 = meter.seconds, time.perf_counter()
    res = fedavg.run_federated_training(series, fcfg, flcfg)[-1]
    jax.block_until_ready(res.params)
    held = synthetic.generate_buildings(
        "CA", list(range(10_000, 10_000 + sizes.heldout)), days=sizes.days)
    metrics = fedavg.evaluate_unseen_clients(res.params, held, fcfg)
    wall = time.perf_counter() - t0
    compile_s = meter.seconds - c0
    hist = res.loss_history
    _check(len(hist) == sizes.rounds and bool(np.all(np.isfinite(hist))),
           f"loss history not finite: {hist.tolist()}")
    _check(hist[-1] < hist[0], f"loss did not fall: {hist.tolist()}")
    _check(_on_tpu(res.params), "trained params are not on the TPU")
    _check(bool(np.isfinite(metrics["mape"])), f"eval metrics {metrics}")
    _emit("train", compile_s, wall - compile_s, wall_s=wall,
          meters=sizes.meters, clients_per_round=sizes.clients_per_round,
          rounds=sizes.rounds, loss_history=hist.tolist(),
          heldout_mape=metrics["mape"], heldout_accuracy=metrics["accuracy"])
    return series, res.params


def _first_update(sizes: Sizes, seed: int, fcfg: ForecasterConfig,
                  series: np.ndarray):
    """The round-0 global model and a slice of the first local update:
    ``ref_clients`` meters, ``ref_steps`` SGD steps of B = 64.  Returns
    ``(params0, (x, y, batch_idx, lr), loss)`` with host arrays.

    The delta of a local update from the round-0 model is large (its
    max-normalised error under one-pass bf16 matmuls is ~2e-3, host
    emulation); from a trained model it is ~12x smaller and the same
    rounding reads ~10x larger, so the comparisons start here."""
    flcfg = _flcfg(sizes, seed)
    params0 = forecaster.init_forecaster(
        jax.random.fold_in(jax.random.PRNGKey(seed), 0), fcfg)
    n = sizes.ref_clients
    prov = windows.ClientWindowProvider.from_series(
        series[:n], fcfg.lookback, fcfg.horizon, cache_size=n)
    x, y, counts = prov.round_batch(np.arange(n))
    bidx = partition.ragged_minibatch_indices(
        np.random.default_rng(seed), counts, sizes.ref_steps,
        flcfg.batch_size)
    return (params0, (x, y, bidx, np.float32(flcfg.lr)),
            losses.make_loss(flcfg.loss, flcfg.beta))


def phase_reference(meter: CompileMeter, sizes: Sizes, seed: int,
                    fcfg: ForecasterConfig, series: np.ndarray,
                    tpu: jax.Device):
    """The first local update on the chip against the same computation in
    f32 on the host CPU backend.  On the chip the one-layer LSTM is
    differentiated by the fused sequence kernels: its program holds a
    ``tpu_custom_call``."""
    params0, data, loss = _first_update(sizes, seed, fcfg, series)
    args = (params0,) + data
    cpu = jax.devices("cpu")[0]
    kw = dict(cfg=fcfg, loss=loss)
    hlo = _local_updates.lower(*jax.device_put(args, tpu), **kw).as_text()
    _check("tpu_custom_call" in hlo,
           "the LSTM local update lowered without a tpu_custom_call")

    c0, t0 = meter.seconds, time.perf_counter()
    on_chip = jax.block_until_ready(
        _local_updates(*jax.device_put(args, tpu), **kw))
    first = time.perf_counter() - t0
    t1 = time.perf_counter()
    jax.block_until_ready(_local_updates(*jax.device_put(args, tpu), **kw))
    steady = time.perf_counter() - t1
    compile_s = meter.seconds - c0
    with _scan_recurrence():
        on_host = jax.block_until_ready(
            _local_updates(*jax.device_put(args, cpu), **kw))
    _check(_on_tpu(on_chip), "reference slice did not run on the TPU")
    d_rel = _rel(_delta(on_chip[0], params0), _delta(on_host[0], params0))
    l_rel = _rel(on_chip[1], on_host[1])
    print(json.dumps({"check": "reference", "max_rel_diff_delta": d_rel,
                      "max_rel_diff_loss": l_rel, "tol": TOL_BF16}),
          flush=True)
    _check(d_rel <= TOL_BF16 and l_rel <= TOL_BF16,
           f"chip vs host f32: delta {d_rel:.3e}, loss {l_rel:.3e} > "
           f"{TOL_BF16}")
    _emit("reference", compile_s, steady, first_call_s=first,
          clients=sizes.ref_clients, steps=sizes.ref_steps,
          max_rel_diff_delta=d_rel, max_rel_diff_loss=l_rel, tol=TOL_BF16,
          tpu_custom_call=True)


def phase_serve(meter: CompileMeter, sizes: Sizes, seed: int,
                fcfg: ForecasterConfig, params):
    """Publish fp32 and int8 weights, warm every bucket, serve raw
    watt-hour requests from unseen consumers."""
    held = synthetic.generate_buildings(
        "CA", list(range(50_000, 50_000 + sizes.consumers)), days=sizes.days)
    L = fcfg.lookback
    # ragged arrivals: full, partial and tiny batches hit several buckets
    cuts = np.cumsum([0] + _chunks(sizes.consumers, sizes.max_batch))
    results, fields = {}, {}
    compile_s = steady = 0.0
    for weights in ("fp32", "int8"):
        reg = ModelRegistry()
        reg.publish(params, fcfg, generation=sizes.rounds, weights=weights,
                    key=(jax.random.fold_in(jax.random.PRNGKey(seed),
                                            sizes.rounds)
                         if weights == "int8" else None))
        eng = ServingEngine(reg, max_batch=sizes.max_batch)
        c0 = meter.seconds
        eng.warmup()
        compile_s += meter.seconds - c0
        warm = eng.jit_cache_size()
        tickets = []

        def step(i, eng=eng, tickets=tickets):
            for j in range(cuts[i], cuts[i + 1]):
                tickets.append(eng.submit(50_000 + j, held[j, -L:],
                                          history=held[j]))
            eng.flush()

        t = time.perf_counter()
        report = recompile.count_recompiles(step, steps=len(cuts) - 2,
                                            cache_size=eng.jit_cache_size)
        steady += time.perf_counter() - t
        _check(report.ok and eng.jit_cache_size() == warm,
               f"{weights}: {report.render()} (cache {warm} -> "
               f"{eng.jit_cache_size()})")
        _check(len(tickets) == sizes.consumers
               and all(tk.done for tk in tickets),
               f"{weights}: not every ticket is done")
        results[weights] = np.stack([tk.result for tk in tickets])
        fields[f"{weights}_batches"] = eng.stats.flushes
        fields[f"{weights}_fill"] = eng.stats.fill()
        fields[f"{weights}_new_jit_entries"] = report.new_entries_per_step

    # the same normalised windows straight through forecaster.forecast
    lo = held.min(axis=1, keepdims=True)
    scale = np.maximum(held.max(axis=1, keepdims=True) - lo, 1e-9)
    xn = ((held[:, -L:] - lo) / scale).astype(np.float32)
    ref = np.concatenate([
        np.asarray(forecaster.forecast(
            params, jnp.asarray(xn[i:i + sizes.max_batch, :, None]), fcfg))
        for i in range(0, sizes.consumers, sizes.max_batch)]) * scale + lo
    fp32_rel = _row_rel(results["fp32"], ref)
    f32, i8 = results["fp32"], results["int8"]
    int8_mape = float(np.mean(np.abs(i8 - f32)
                              / np.maximum(np.abs(f32), 1e-6)))
    print(json.dumps({"check": "serve", "fp32_max_rel_diff": fp32_rel,
                      "tol": TOL_BF16, "int8_mape_delta": int8_mape,
                      "int8_bound": INT8_MAPE_BOUND}), flush=True)
    _check(fp32_rel <= TOL_BF16,
           f"fp32 serving vs forecast: {fp32_rel:.3e} > {TOL_BF16}")
    _check(int8_mape < INT8_MAPE_BOUND,
           f"int8 MAPE delta {int8_mape:.4f} >= {INT8_MAPE_BOUND}")
    _emit("serve", compile_s, steady, consumers=sizes.consumers,
          fp32_max_rel_diff=fp32_rel, int8_mape_delta=int8_mape, **fields)


def _chunks(n: int, max_batch: int):
    """Arrival sizes for ``n`` requests: one full batch, two ragged ones
    (smaller buckets) and the rest."""
    head = [max_batch, max_batch * 2 // 5, max_batch // 7]
    return head + [n - sum(head)]


def phase_mesh(meter: CompileMeter, sizes: Sizes, seed: int,
               fcfg: ForecasterConfig):
    """One R1 round on a flat and a 2x2 hierarchical mesh over four chips
    against the one-chip vmap round on the same selection, and a DP +
    ring-masked int8 hierarchical round against its ring-clear comparator."""
    devs = jax.devices()
    _check(len(devs) == 4, f"--four-chips needs 4 devices, found {len(devs)}")
    n, m = sizes.mesh_meters, sizes.clients_per_round
    series = synthetic.generate_buildings("CA", list(range(n)),
                                          days=sizes.days)
    prov = windows.ClientWindowProvider.from_series(
        series, fcfg.lookback, fcfg.horizon, cache_size=n)
    flcfg = _flcfg(sizes, seed, n_clients=n, rounds=1)
    e_one = fedavg.RoundEngine(fcfg, flcfg)
    rng = np.random.default_rng(seed)
    sel = e_one.select(rng, np.arange(n), m, 0)
    x, y, counts = prov.round_batch(sel)
    steps = partition.local_steps(prov.n_win_max, flcfg.batch_size, 1)
    bidx = partition.ragged_minibatch_indices(rng, counts, steps,
                                              flcfg.batch_size)
    params, s0 = e_one.init(jax.random.PRNGKey(seed))

    def round_on(engine, sl=slice(None)):
        args = engine.put_clients(x[sl], y[sl], bidx[sl])
        c0 = meter.seconds
        p, _, l = jax.block_until_ready(
            engine.step(params, s0, *args, counts[sl], round_idx=0))
        t = time.perf_counter()
        jax.block_until_ready(engine.step(params, s0, *args, counts[sl],
                                          round_idx=0))
        return p, l, meter.seconds - c0, time.perf_counter() - t

    p1, l1, c1, s1 = round_on(e_one)
    out = {"one_chip": {"compile_s": c1, "steady_s": s1}}
    for kind in ("flat", "hierarchical"):
        cfg = _flcfg(sizes, seed, n_clients=n, rounds=1, aggregation=kind)
        mesh = aggregation.make_mesh(cfg)
        _check(len(mesh.devices.flat) == 4,
               f"{kind} mesh spans {mesh.devices.size} devices")
        if kind == "hierarchical":
            _check(dict(mesh.shape) == {"region": 2, "clients": 2},
                   f"hierarchical mesh is {dict(mesh.shape)}, not 2x2")
        p, l, c, s = round_on(fedavg.RoundEngine(fcfg, cfg, mesh=mesh))
        d_rel = _rel(_delta(p, params), _delta(p1, params))
        l_rel = _rel(l, l1)
        _check(d_rel <= TOL_MESH and l_rel <= TOL_MESH,
               f"{kind} mesh vs one chip: delta {d_rel:.3e}, loss "
               f"{l_rel:.3e} > {TOL_MESH}")
        out[kind] = {"mesh": dict(mesh.shape), "compile_s": c,
                     "steady_s": s, "max_rel_diff_delta": d_rel,
                     "max_rel_diff_loss": l_rel, "tol": TOL_MESH}

    k = sizes.dp_clients
    dp = dict(n_clients=n, rounds=1, clients_per_round=k, dp_clip=1.0,
              dp_noise=0.5, quantize_bits=8, aggregation="hierarchical")
    clear_cfg = _flcfg(sizes, seed, **dp, quantize_ring=True)
    mesh = aggregation.make_mesh(clear_cfg)
    p_c, l_c, cc, sc = round_on(
        fedavg.RoundEngine(fcfg, clear_cfg, mesh=mesh), slice(0, k))
    p_m, l_m, cm, sm = round_on(
        fedavg.RoundEngine(fcfg, _flcfg(sizes, seed, **dp, secure_agg=True),
                           mesh=mesh), slice(0, k))
    same = (np.array_equal(np.asarray(l_c), np.asarray(l_m))
            and all(np.array_equal(np.asarray(a), np.asarray(b))
                    for a, b in zip(jax.tree.leaves(p_c),
                                    jax.tree.leaves(p_m))))
    _check(same, "ring-masked hierarchical round != ring-clear comparator")
    out["ring_masked_vs_clear"] = {"clients": k, "bit_exact": True,
                                   "compile_s": cc + cm, "steady_s": sc + sm}
    _emit("mesh", sum(v["compile_s"] for v in out.values()),
          sum(v["steady_s"] for v in out.values()), clients_per_round=m,
          rounds=out)


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh rounds over four chips and the "
                    "one-chip round they are compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    cache_dir = enable_compile_cache()
    meter = CompileMeter()
    dev = phase_device(cache_dir)
    sizes, fcfg = Sizes(), ForecasterConfig()
    if args.four_chips:
        phase_mesh(meter, sizes, args.seed, fcfg)
    else:
        series, params = phase_train(meter, sizes, args.seed, fcfg)
        phase_reference(meter, sizes, args.seed, fcfg, series, dev)
        phase_serve(meter, sizes, args.seed, fcfg, params)
    print(json.dumps({"phase": "compile_cache", "dir": cache_dir,
                      "requests": meter.cache_requests,
                      "hits": meter.cache_hits}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
