"""Traffic kind ``serve_open_loop``: forecast requests from a resident
consumer population, offered in real time at a fixed Poisson rate to
``ServingEngine.submit`` and ``ServingEngine.flush`` in this one process.

The trace model is ``benchmarks/bench_serving.py``'s, paced by the wall
clock: arrival times are the sorted uniform draws of a Poisson process
holding ``rate * seconds`` requests; a request is submitted once it is due;
the slot is flushed when its bucket fills or when its oldest request has
waited ``max_wait_ms``; a share of requests are first contacts that ship a
raw history; one registry publish of a new generation lands halfway
through.  Each request is timed from when it was due to when its result
arrived, and the generator records how late it submitted each one.

Consumers are drawn from a pool of synthetic meters: consumer ``c`` is pool
meter ``b_c`` scaled by ``s_c`` (log-normal), both from the seed.  Its
history is the first ``history_days`` days; its request windows are the
``lookback`` readings from a uniformly drawn position of the day after.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import compare
from bench.references import forecaster as ref

STEPS_PER_DAY = 96


class Run:
    def __init__(self, model: dict, traffic: dict, seed: int):
        from repro.configs.base import ForecasterConfig
        from repro.data import synthetic

        self.model, self.traffic, self.seed = model, traffic, seed
        t = traffic
        self.fcfg = ForecasterConfig(**{k: model[k] for k in (
            "cell", "input_dim", "hidden_dim", "n_layers", "lookback",
            "horizon")})
        rng = np.random.default_rng(np.random.SeedSequence([seed, 2]))
        pool_ids = rng.choice(t["population"], t["pool_meters"],
                              replace=False)
        hist = t["history_days"] * STEPS_PER_DAY
        self.pool = synthetic.generate_buildings(
            t["state"], pool_ids.tolist(), days=t["history_days"] + 1)
        R = t["residents"]
        n_req = int(round(t["rate"] * t["seconds"]))
        n_new = int(round(t["first_contact_share"] * n_req))
        n_cons = R + n_new
        self.meter_of = rng.integers(0, t["pool_meters"], n_cons)
        self.scale_of = np.exp(rng.normal(0.0, t["scale_sigma"],
                                          n_cons)).astype(np.float32)
        # a positive scale keeps the order of readings, so each consumer's
        # history range is its meter's range scaled, bit for bit
        pool_hist = self.pool[:, :t["history_days"] * STEPS_PER_DAY]
        self.hist_lo = pool_hist.min(axis=1)[self.meter_of] * self.scale_of
        self.hist_hi = pool_hist.max(axis=1)[self.meter_of] * self.scale_of
        # the schedule: due times, consumer, window position, first contact
        self.due = np.sort(rng.uniform(0.0, t["seconds"], n_req))
        self.first = np.zeros(n_req, bool)
        self.first[rng.choice(n_req, n_new, replace=False)] = True
        cid = rng.integers(0, R, n_req)
        cid[self.first] = R + np.arange(n_new)
        self.cid = cid
        L = model["lookback"]
        pos = rng.integers(hist, hist + STEPS_PER_DAY - L + 1, n_req)
        idx = pos[:, None] + np.arange(L)
        self.win = (self.pool[self.meter_of[cid][:, None], idx]
                    * self.scale_of[cid][:, None]).astype(np.float32)
        self.hist_len = hist
        # weights of both generations, made on the device in one call
        self.params = jax.block_until_ready(ref.make_weights(
            jax.random.PRNGKey(seed % 2 ** 31),
            tuple(sorted(model.items())), (1, 2)))

    def history(self, c: int) -> np.ndarray:
        return self.pool[self.meter_of[c], :self.hist_len] * self.scale_of[c]

    # ------------------------------------------------------------ program
    def warm(self) -> None:
        """Publish generation 1, compile every bucket, and make first
        contact with the resident population."""
        from repro.serving import ModelRegistry, ServingEngine
        t = self.traffic
        self.registry = ModelRegistry()
        self.registry.publish(self.params[0], self.fcfg, generation=1)
        self.engine = ServingEngine(
            self.registry, max_batch=t["max_batch"],
            min_bucket=t["min_bucket"], auto_flush=False,
            consumer_cache=t["consumer_cache"])
        self.engine.warmup()
        L = self.model["lookback"]
        for c in range(t["residents"]):
            h = self.history(c)
            self.engine.submit(c, h[-L:], history=h)
            if self.engine.pending() >= t["max_batch"]:
                self.engine.flush()
        self.engine.flush()

    def window(self, seconds: float, annotate: bool = False) -> dict:
        """Offer the schedule in real time; flush on a full bucket or an
        expired deadline; publish generation 2 halfway through."""
        t = self.traffic
        eng = self.engine
        n = len(self.due)
        due, first, cid, win = self.due, self.first, self.cid, self.win
        max_batch, max_wait = t["max_batch"], t["max_wait_ms"] * 1e-3
        swap_at = seconds / 2
        # a ticket is read and dropped at its flush, as a client would: a
        # window's worth of live tickets would make every full collection
        # of the garbage collector scan them all
        tickets = [None] * n
        results = np.full((n, self.model["horizon"]), np.nan, np.float32)
        submit_t = np.empty(n)
        done_t = np.empty(n)
        served_gen = np.empty(n, np.int8)
        gen = 1
        stats0 = (eng.stats.requests, eng.stats.flushes, eng.stats.busy_s,
                  dict(eng.stats.by_bucket))
        span = (jax.profiler.TraceAnnotation if annotate
                else lambda name: contextlib.nullcontext())
        clock = time.perf_counter
        i = head = 0
        t0 = clock()

        def flush(now_i):
            nonlocal head
            with span("bench.flush"):
                eng.flush()
            t_done = clock() - t0
            done_t[head:now_i] = t_done
            served_gen[head:now_i] = gen
            for k in range(head, now_i):
                if tickets[k].done:
                    results[k] = tickets[k].result
                tickets[k] = None
            head = now_i

        while i < n:
            now = clock() - t0
            if gen == 1 and now >= swap_at:
                with span("bench.publish"):
                    self.registry.publish(self.params[1], self.fcfg,
                                          generation=2)
                gen = 2
            while i < n and due[i] <= now:
                c = int(cid[i])
                tickets[i] = eng.submit(
                    c, win[i], history=self.history(c) if first[i] else None)
                submit_t[i] = clock() - t0
                i += 1
                if i - head >= max_batch:
                    flush(i)
                now = clock() - t0
            if i > head and now - due[head] >= max_wait:
                flush(i)
        if i > head:
            flush(i)
        window_s = clock() - t0
        self.results, self.served_gen = results, served_gen
        s = eng.stats
        by_bucket = {b: k - stats0[3].get(b, 0)
                     for b, k in s.by_bucket.items()}
        padded = sum(b * k for b, k in by_bucket.items())
        served = int((~np.isnan(results).any(axis=1)).sum())
        return {
            "window_s": window_s, "attempted": n, "failed": n - served,
            "served": served, "latency_s": done_t - due,
            "lag_s": submit_t - due, "flushes": s.flushes - stats0[1],
            "busy_s": s.busy_s - stats0[2],
            "fill": (s.requests - stats0[0]) / padded if padded else None,
        }

    def free(self) -> None:
        self.engine = self.registry = None

    # ---------------------------------------------------------- reference
    def stats(self):
        """Each request's (lo, hi), as the engine's documented consumer
        cache gives them: the history's range after a first contact, held
        in an LRU of ``consumer_cache`` consumers; a consumer that has
        fallen out of it is normalised by its window's own range."""
        t = self.traffic
        cap = t["consumer_cache"]
        lru = collections.OrderedDict.fromkeys(range(t["residents"]))
        while len(lru) > cap:
            lru.popitem(last=False)
        n = len(self.due)
        # the consumer whose history gives each request its range; -1 for
        # a window normalised by its own range
        src = np.full(n, -1, np.int64)
        for i, (c, new) in enumerate(zip(self.cid.tolist(),
                                         self.first.tolist())):
            if new or c in lru:
                lru[c] = None
                lru.move_to_end(c)
                if len(lru) > cap:
                    lru.popitem(last=False)
                src[i] = c
        own = src < 0
        lo = np.where(own, self.win.min(axis=1), self.hist_lo[src])
        hi = np.where(own, self.win.max(axis=1), self.hist_hi[src])
        return lo.astype(np.float32), hi.astype(np.float32)

    def reference(self, dtype=jnp.float32) -> np.ndarray:
        lo, hi = self.lohi
        out = np.empty((len(self.due), self.model["horizon"]), np.float32)
        gens = self.served_gen
        for g in (1, 2):
            rows = np.flatnonzero(gens == g)
            if len(rows):
                out[rows] = ref.serve(self.params[g - 1], self.win[rows],
                                      lo[rows, None], hi[rows, None],
                                      self.model, dtype)
        return out

    @functools.cached_property
    def lohi(self):
        return self.stats()

    @functools.cached_property
    def ref32(self):
        return self.reference()

    def numbers(self, got: np.ndarray) -> dict:
        lo, hi = self.lohi
        return {"forecast_gap": compare.forecast_gap(got, self.ref32, lo, hi),
                "missing": int(np.isnan(got).any(axis=1).sum())}


def check(run: Run, limits: dict) -> list:
    got = run.numbers(run.results)
    return [(k, got[k], limits[k]) for k in ("forecast_gap", "missing")]


def readings(run: Run, variant: str) -> dict:
    if variant == "program":
        return run.numbers(run.results)
    if variant == "control":
        return run.numbers(run.reference(jnp.bfloat16))
    raise ValueError(f"serve_open_loop has no fault variant {variant!r}")
