"""Traffic kind ``fl_backbone``: cross-silo federated training of a large
forecasting backbone through the program's own host round loop,
``fedavg.run_federated_training``, with the model spec the configuration
names (``repro.configs.base.HybridForecasterConfig``).

Set-up generates the fleet's raw series on the host from the seed, as
``fl_round`` does, wraps it once in a ``ClientWindowProvider`` with no
cache, and drives the loop twice: one round, then ``check_rounds`` rounds
from the same seed.  Those are the readings the reference checks, and they
warm every program the window runs.  Each client runs ``local_steps`` SGD
steps of ``batch_size`` windows drawn uniformly from its stride-1 windows.
The window then repeats whole calls of ``rounds_per_call`` rounds until
``--seconds`` have passed; each call takes the next program seed.  One
operation is one round; one sample is one window of ``lookback + horizon``
readings.

The parameters never come to the host: the compared change norms are
taken on the device, per weight and per layer, as each reading is made.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import compare
from bench.harness.fl_round import _PROGRAM_SEED_MOD
from bench.references import hybrid_forecaster as ref


def spec_of(model: dict):
    """The program's model spec for a configuration's ``model`` block."""
    from repro.configs.base import HybridForecasterConfig, SSMConfig
    m = {k: v for k, v in model.items() if k != "dtype"}
    return HybridForecasterConfig(**{**m, "ssm": SSMConfig(**m["ssm"]),
                                     "layer_types": tuple(m["layer_types"])})


def _layer_names(path: str, n: int, start: int) -> list:
    """``segments/<s>/<rest>`` of a stacked leaf -> ``layers/<i>/<rest>``."""
    rest = path.split("/", 2)[2]
    return [f"layers/{start + j}/{rest}" for j in range(n)]


@jax.jit
def _leaf_norms(before, after):
    """Per leaf, the L2 norm of the change over all axes but the first of
    a stacked leaf (one per layer), over all axes otherwise."""
    def one(b, a, stacked):
        d = (a - b).astype(jnp.float32)
        return (jnp.sqrt(jnp.sum(d * d, axis=tuple(range(1, d.ndim))))
                if stacked else jnp.linalg.norm(d.reshape(-1)))
    return {"segments": jax.tree.map(lambda b, a: one(b, a, True),
                                     before["segments"], after["segments"]),
            **{k: jax.tree.map(lambda b, a: one(b, a, False), before[k],
                               after[k])
               for k in before if k != "segments"}}


def change_norms(spec, before, after) -> dict:
    """L2 norm of each weight's change, named as the reference names it
    (``layers/<i>/...`` for the stacked segments)."""
    from repro.models import hybrid_forecaster
    starts = [s for _, s, _ in hybrid_forecaster.segments(spec)]
    out = {}
    for kp, v in jax.tree_util.tree_flatten_with_path(
            _leaf_norms(before, after))[0]:
        path = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in kp)
        v = np.asarray(v, np.float64)
        if path.startswith("segments/"):
            seg = int(path.split("/")[1])
            out.update(zip(_layer_names(path, v.shape[0], starts[seg]), v))
        else:
            out[path] = float(v)
    return out


class Run:
    """One cell's fleet, program configuration and set-up readings."""

    def __init__(self, model: dict, traffic: dict, seed: int):
        from repro.configs.base import FLConfig
        from repro.data import synthetic, windows

        self.model, self.traffic, self.seed = model, traffic, seed
        self.pseed = seed % _PROGRAM_SEED_MOD
        t = traffic
        rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
        ids = np.sort(rng.choice(t["population"], t["meters"], replace=False))
        self.series = np.asarray(synthetic.generate_buildings(
            t["state"], ids.tolist(), days=t["days"]))
        self.spec = spec_of(model)
        self.provider = windows.ClientWindowProvider.from_series(
            self.series, self.spec.lookback, self.spec.horizon,
            train_frac=t["train_frac"], cache_size=0)
        self.flcfg = FLConfig(
            n_clients=t["meters"], clients_per_round=t["clients_per_round"],
            rounds=t["rounds_per_call"], local_steps=t["local_steps"],
            batch_size=t["batch_size"], lr=t["lr"], loss=t["loss"],
            beta=t["beta"], n_clusters=0, seed=self.pseed,
            server_opt=t["server_opt"], sampling=t["sampling"],
            aggregation=t["aggregation"], mode=t["mode"])
        self.samples_per_round = (t["clients_per_round"] * t["local_steps"]
                                  * t["batch_size"])

    # ------------------------------------------------------------ program
    def _call(self, offset: int = 0, **kw):
        from repro.core import fedavg
        rounds = kw.pop("rounds", self.flcfg.rounds)
        flcfg = dataclasses.replace(
            self.flcfg, rounds=rounds,
            seed=(self.pseed + offset) % _PROGRAM_SEED_MOD)
        res = fedavg.run_federated_training(self.provider, self.spec,
                                            flcfg, **kw)[-1]
        jax.block_until_ready(res.params)
        return res

    def _norms_from_init(self, params) -> dict:
        key = jax.random.fold_in(jax.random.PRNGKey(self.pseed), 0)
        return change_norms(self.spec, self.spec.init(key), params)

    def warm(self) -> None:
        """The set-up calls: one round, then ``check_rounds`` rounds from
        the same seed.  Keeps what the check compares: the change norms
        after the first and the last round, and the round losses."""
        self.n1 = self._norms_from_init(
            self._call(stop_after_rounds=1).params)
        res = self._call(rounds=self.traffic["check_rounds"])
        self.nn = self._norms_from_init(res.params)
        self.losses = [float(v) for v in res.loss_history]

    def window(self, seconds: float, annotate: bool = False) -> dict:
        """Whole calls until ``seconds`` have passed."""
        span = (jax.profiler.TraceAnnotation if annotate
                else lambda name: contextlib.nullcontext())
        calls = 0
        t0 = time.perf_counter()
        while True:
            calls += 1
            with span("bench.fl_call"):
                self._call(offset=calls)
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
        rounds = calls * self.flcfg.rounds
        return {"window_s": elapsed, "rounds": rounds, "calls": calls,
                "samples": rounds * self.samples_per_round,
                "attempted": rounds, "failed": 0}

    def free(self) -> None:
        self.provider = None

    # ---------------------------------------------------------- reference
    def _fl(self) -> dict:
        t = self.traffic
        return {k: t[k] for k in ("clients_per_round", "local_steps",
                                  "batch_size", "lr", "beta", "train_frac")}

    def reference(self, dtype=jnp.float32, fault: str = ""):
        """The reference's change norms after round 1 and after the last
        compared round, and its round losses."""
        norms, losses = ref.fedavg_rounds(
            self.series, self.pseed, self.model, self._fl(), len(self.losses),
            dtype, fault)
        return norms[0], norms[-1], losses

    def numbers(self, side) -> dict:
        """The compared numbers of ``side`` (n1, nn, losses) against the
        float32 reference."""
        r1, rn, rl = self.ref32
        p1, pn, pl = side
        names = sorted(r1)
        at = lambda d: [d[k] for k in names]
        return {"loss_gap": compare.loss_gap(pl, rl),
                "grad1_gap": compare.norm_gap(at(p1), at(r1)),
                "change_gap": compare.norm_gap(at(pn), at(rn))}

    @functools.cached_property
    def ref32(self):
        return self.reference()

    def program_side(self):
        return self.n1, self.nn, self.losses


def check(run: Run, limits: dict) -> list:
    """(name, value, limit) of each compared number the mix gives a limit:
    the program's set-up calls against the float32 reference."""
    got = run.numbers(run.program_side())
    return [(k, got[k], limits[k]) for k in ("loss_gap", "grad1_gap",
                                             "change_gap") if k in limits]


def readings(run: Run, variant: str) -> dict:
    """The compared numbers of a variant: ``program``; ``control`` (the
    reference in bfloat16); or a planted fault in the reference
    (``half_batch``)."""
    if variant == "program":
        return run.numbers(run.program_side())
    if variant == "control":
        return run.numbers(run.reference(jnp.bfloat16))
    return run.numbers(run.reference(jnp.float32, variant))
