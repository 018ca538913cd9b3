"""Traffic kind ``fl_round``: federated training through the program's own
host round loop, ``fedavg.run_federated_training``.

Set-up generates the fleet's raw series on the host from the seed, wraps it
once in a ``ClientWindowProvider`` with no cache (each round windows its own
cohort, as a fleet too large for host memory must), and drives the loop
twice: one round, then one whole call.  Those are the readings the
reference checks, and they warm every program the window runs.  The window
then repeats whole calls of ``rounds_per_call`` rounds until ``--seconds``
have passed; it ends when the last call ends.  Each window call takes the
next program seed, so its cohorts and minibatches differ from every other
call's.  One operation is one round.
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
from bench.references import forecaster as ref

_PROGRAM_SEED_MOD = 2 ** 31        # FLConfig.seed feeds int32 PRNG keys


def _tree_np(tree):
    return [np.asarray(a, np.float64) for a in jax.tree.leaves(tree)]


class Run:
    """One cell's fleet, program configuration and set-up readings."""

    def __init__(self, model: dict, traffic: dict, seed: int):
        from repro.configs.base import FLConfig, ForecasterConfig
        from repro.data import synthetic, windows

        self.model, self.traffic, self.seed = model, traffic, seed
        self.pseed = seed % _PROGRAM_SEED_MOD
        t = traffic
        rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
        ids = np.sort(rng.choice(t["population"], t["meters"], replace=False))
        self.series = synthetic.generate_buildings(t["state"], ids.tolist(),
                                                   days=t["days"])
        self.fcfg = ForecasterConfig(**{k: model[k] for k in (
            "cell", "input_dim", "hidden_dim", "n_layers", "lookback",
            "horizon")})
        self.provider = windows.ClientWindowProvider.from_series(
            self.series, self.fcfg.lookback, self.fcfg.horizon,
            train_frac=t["train_frac"], cache_size=0)
        self.flcfg = FLConfig(
            n_clients=t["meters"], clients_per_round=t["clients_per_round"],
            rounds=t["rounds_per_call"], local_epochs=t["local_epochs"],
            batch_size=t["batch_size"], lr=t["lr"], loss=t["loss"],
            beta=t["beta"], n_clusters=0, seed=self.pseed,
            server_opt=t["server_opt"], sampling=t["sampling"],
            aggregation=t["aggregation"], mode=t["mode"])
        self.steps = -(-int(self.provider.n_win_max) // t["batch_size"]) \
            * t["local_epochs"]
        self.samples_per_round = (t["clients_per_round"] * self.steps
                                  * t["batch_size"])

    # ------------------------------------------------------------ program
    def _call(self, offset: int = 0, **kw):
        from repro.core import fedavg
        flcfg = dataclasses.replace(
            self.flcfg, seed=(self.pseed + offset) % _PROGRAM_SEED_MOD)
        res = fedavg.run_federated_training(self.provider, self.fcfg,
                                            flcfg, **kw)[-1]
        jax.block_until_ready(res.params)
        return res

    def warm(self) -> None:
        """The set-up calls: one round, then one whole call.  Keeps what
        the check compares: the initial, first-round and last-round
        parameters and the round losses."""
        from repro.models import forecaster
        key = jax.random.fold_in(jax.random.PRNGKey(self.pseed), 0)
        self.w0 = _tree_np(forecaster.init_forecaster(key, self.fcfg))
        self.w1 = _tree_np(self._call(stop_after_rounds=1).params)
        res = self._call()
        self.wn = _tree_np(res.params)
        self.losses = [float(v) for v in res.loss_history]

    def window(self, seconds: float, annotate: bool = False) -> dict:
        """Whole calls until ``seconds`` have passed."""
        if annotate:
            self._annotate_provider()
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

    def _annotate_provider(self) -> None:
        """A host span around the host windowing of each round's cohort."""
        inner = self.provider.round_batch

        def round_batch(ids):
            with jax.profiler.TraceAnnotation("bench.round_batch"):
                return inner(ids)
        self.provider.round_batch = round_batch

    def free(self) -> None:
        self.provider = None

    # ---------------------------------------------------------- reference
    def _fl(self) -> dict:
        t = self.traffic
        return {k: t[k] for k in ("clients_per_round", "batch_size", "lr",
                                  "beta", "train_frac")}

    def reference(self, dtype=jnp.float32, fault: str = ""):
        """The reference's initial parameters, its parameters after round
        1 and after the last compared round, and its round losses."""
        n = len(self.losses)
        params, losses = ref.fedavg_rounds(
            self.series, self.pseed, self.model, self._fl(), n, dtype, fault)
        w0 = _tree_np(ref.init_params(self.pseed, self.model))
        return w0, _tree_np(params[0]), _tree_np(params[-1]), losses

    def numbers(self, side) -> dict:
        """The compared numbers of ``side`` (w0, w1, wn, losses) against
        the float32 reference."""
        r0, r1, rn, rl = self.ref32
        p0, p1, pn, pl = side
        return {
            "loss_gap": compare.loss_gap(pl, rl),
            "grad1_gap": compare.norm_gap(compare.leaf_norms(p0, p1),
                                          compare.leaf_norms(r0, r1)),
            "change_gap": compare.norm_gap(compare.leaf_norms(p0, pn),
                                           compare.leaf_norms(r0, rn)),
        }

    @functools.cached_property
    def ref32(self):
        return self.reference()

    def program_side(self):
        return self.w0, self.w1, self.wn, self.losses


def check(run: Run, limits: dict) -> list:
    """(name, value, limit) of each compared number: the program's set-up
    calls against the float32 reference."""
    got = run.numbers(run.program_side())
    return [(k, got[k], limits[k]) for k in ("loss_gap", "grad1_gap",
                                             "change_gap")]


def readings(run: Run, variant: str) -> dict:
    """The compared numbers of a variant: ``program``; ``control`` (the
    reference in bfloat16); or a planted fault in the reference
    (``half_batch``, ``no_exchange``)."""
    if variant == "program":
        return run.numbers(run.program_side())
    if variant == "control":
        return run.numbers(run.reference(jnp.bfloat16))
    return run.numbers(run.reference(jnp.float32, variant))
