"""Padded-bucket batching engine — the serving tier's hot path.

Forecast requests arrive one consumer at a time as RAW watt-hour windows;
the engine owns everything between that and the jit-compiled forward:

* **Coalescing**: requests queue per model slot (the router's cluster id)
  and are served in batches of at most ``max_batch``.
* **Power-of-two shape buckets**: each batch is zero-padded UP to the next
  power-of-two bucket in ``[min_bucket, max_batch]``, so a steady stream of
  ragged request counts presents XLA with a BOUNDED set of shapes
  (≤ log2(max_batch/min_bucket)+1 per weights kind) instead of one fresh
  compile per distinct count.  :meth:`ServingEngine.warmup` pre-compiles
  every bucket; after it, the steady state adds ZERO new jit-cache entries
  — enforced with the :func:`repro.analysis.recompile.count_recompiles`
  probe against :meth:`ServingEngine.jit_cache_size` (tests + bench).
* **Per-request normalization inside the engine**: callers send raw
  watt-hours plus (once per consumer) a raw history; the engine derives the
  consumer's min-max stats, normalizes INSIDE the jitted forward, and
  de-normalizes the forecast back to kWh — the jit boundary sees only
  fixed-shape f32 buffers, and callers never touch model space.
* **Hot-swap safety**: a flush snapshots its :class:`ModelHandle` ONCE and
  serves the whole batch from it; a registry publish lands at the next
  flush boundary, never mid-batch.  Model parameters are TRACED jit
  arguments, so a swap never recompiles.
"""
from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import forecaster
from repro.serving.registry import (GLOBAL_SLOT, ModelHandle, ModelRegistry,
                                    dequantize_params)

__all__ = ["ForecastRequest", "FlushStats", "EngineStats", "ServingEngine",
           "bucket_for", "bucket_ladder"]


def bucket_for(n: int, min_bucket: int, max_batch: int) -> int:
    """Power-of-two bucket for ``n`` requests, clamped to
    ``[min_bucket, max_batch]``.  ``n`` must fit one batch."""
    if n < 1 or n > max_batch:
        raise ValueError(f"n={n} outside [1, max_batch={max_batch}]")
    b = 1 << max(n - 1, 0).bit_length()
    return min(max(b, min_bucket), max_batch)


def bucket_ladder(min_bucket: int, max_batch: int) -> List[int]:
    """All bucket sizes the engine can emit: min_bucket, 2·min_bucket, …,
    max_batch."""
    out, b = [], min_bucket
    while b < max_batch:
        out.append(b)
        b *= 2
    return out + [max_batch]


@dataclasses.dataclass
class ForecastRequest:
    """One pending forecast; doubles as the caller's result ticket.

    ``window`` is the consumer's most recent ``lookback`` RAW watt-hour
    readings; ``result`` is the (horizon,) kWh forecast once flushed.
    """
    consumer_id: Any
    window: np.ndarray
    lo: float
    hi: float
    slot: Any
    result: Optional[np.ndarray] = None

    @property
    def done(self) -> bool:
        return self.result is not None


@dataclasses.dataclass(frozen=True)
class FlushStats:
    """One executed batch: who ran, how padded, and how long it took."""
    slot: Any
    n_requests: int                       # real rows
    bucket: int                           # padded shape actually executed
    wall_s: float                         # measured device time (blocked)
    generation: int                       # handle generation that served it
    weights: str                          # "fp32" | "int8"
    requests: Tuple[ForecastRequest, ...] = ()


@dataclasses.dataclass
class EngineStats:
    requests: int = 0
    flushes: int = 0
    busy_s: float = 0.0
    swaps_seen: int = 0                   # generation changes across flushes
    by_bucket: Dict[int, int] = dataclasses.field(default_factory=dict)

    def fill(self) -> float:
        """Mean batch occupancy: real rows / padded rows across flushes."""
        padded = sum(b * n for b, n in self.by_bucket.items())
        return self.requests / padded if padded else 0.0


# jit bodies are module-level so every engine shares one trace per
# (shape-bucket, cfg, weights).  Nothing is donated: no input buffer has the
# (B, horizon) output's shape, so XLA could reuse none of them.
def _forecast_kwh(params, x, lo, hi, cfg):
    """(B, L) raw watt-hours + per-row (lo, hi) stats -> (B, H) kWh."""
    scale = jnp.maximum(hi - lo, 1e-9)
    xn = (x - lo) / scale
    pred = forecaster.forecast(params, xn[..., None], cfg)
    return pred * scale + lo


def _forecast_kwh_int8(qparams, x, lo, hi, cfg):
    # dequantize INSIDE the jit: the fp32 copy is an XLA temporary
    return _forecast_kwh(dequantize_params(qparams), x, lo, hi, cfg)


class ServingEngine:
    """Queue + bucketed-batch executor over a :class:`ModelRegistry`.

    ``router`` (a :class:`repro.serving.router.ClusterRouter`) maps a
    consumer's raw history to a model slot at first contact; without one
    (or without a history) everything runs on the global slot.  Consumer
    stats/slot assignments live in a bounded LRU (``consumer_cache``).

    ``auto_flush`` flushes a slot the moment its queue reaches
    ``max_batch``; replay harnesses that account queueing time themselves
    (``benchmarks/bench_serving.py``) turn it off and drive
    :meth:`flush` explicitly.
    """

    def __init__(self, registry: ModelRegistry, router=None, *,
                 max_batch: int = 256, min_bucket: int = 8,
                 auto_flush: bool = True, consumer_cache: int = 100_000):
        for name, v in (("max_batch", max_batch), ("min_bucket", min_bucket)):
            if v < 1 or v & (v - 1):
                raise ValueError(f"{name}={v} must be a power of two")
        if min_bucket > max_batch:
            raise ValueError(f"min_bucket={min_bucket} > max_batch={max_batch}")
        self.registry = registry
        self.router = router
        self.max_batch, self.min_bucket = int(max_batch), int(min_bucket)
        self.auto_flush = bool(auto_flush)
        self.stats = EngineStats()
        self._queues: Dict[Any, List[ForecastRequest]] = {}
        self._consumers: "OrderedDict[Any, Tuple[Any, float, float]]" = \
            OrderedDict()
        self._consumer_cache = int(consumer_cache)
        self._last_gen: Dict[Any, int] = {}
        self._fp32 = jax.jit(_forecast_kwh, static_argnames=("cfg",))
        self._int8 = jax.jit(_forecast_kwh_int8, static_argnames=("cfg",))

    # -------------------------------------------------------------- probes
    def jit_cache_size(self) -> int:
        """Live jit-cache entries across both weight paths — the probe
        ``analysis.recompile.count_recompiles`` pins the zero-new-entries
        steady-state contract against."""
        return int(self._fp32._cache_size() + self._int8._cache_size())

    def pending(self, slot: Any = None) -> int:
        if slot is not None:
            return len(self._queues.get(slot, ()))
        return sum(len(q) for q in self._queues.values())

    def queued_slots(self) -> List[Any]:
        """Slots with at least one pending request (replay-harness hook)."""
        return [s for s, q in self._queues.items() if q]

    def oldest(self, slot: Any) -> Optional[ForecastRequest]:
        """Head of a slot's queue (None when empty) — what a deadline-based
        flush policy ages against."""
        q = self._queues.get(slot)
        return q[0] if q else None

    # -------------------------------------------------------------- intake
    def _resolve(self, consumer_id, window: np.ndarray,
                 history) -> Tuple[Any, float, float]:
        """(slot, lo, hi) for one consumer: cached after first contact.

        With a raw ``history`` the min-max stats come from the full history
        (matching training-side per-building normalization) and the router
        assigns the cluster slot from its privacy-coarsened daily summary.
        Without either, the request window's own min-max is the documented
        fallback — fine for flat consumers, coarse for peaky ones.
        """
        if consumer_id is not None and history is None:
            hit = self._consumers.get(consumer_id)
            if hit is not None:
                self._consumers.move_to_end(consumer_id)
                return hit
        if history is not None:
            h = np.asarray(history, np.float32).reshape(-1)
            lo, hi = float(h.min()), float(h.max())
            slot = (self.router.route(h)
                    if self.router is not None and self.router.enabled
                    else GLOBAL_SLOT)
        else:
            lo, hi = float(window.min()), float(window.max())
            slot = GLOBAL_SLOT
        entry = (slot, lo, hi)
        if consumer_id is not None and history is not None \
                and self._consumer_cache > 0:
            self._consumers[consumer_id] = entry
            while len(self._consumers) > self._consumer_cache:
                self._consumers.popitem(last=False)
        return entry

    def submit(self, consumer_id, window, history=None) -> ForecastRequest:
        """Enqueue one forecast request (raw watt-hours) and return its
        ticket.  Pass ``history`` on a consumer's first contact so routing
        and normalization use their real range; later requests hit the
        consumer cache."""
        w = np.asarray(window, np.float32).reshape(-1)
        slot, lo, hi = self._resolve(consumer_id, w, history)
        handle = self.registry.handle(slot)
        if w.shape[0] != handle.cfg.lookback:
            raise ValueError(
                f"window has {w.shape[0]} readings; slot {handle.slot!r} "
                f"model wants lookback={handle.cfg.lookback}")
        req = ForecastRequest(consumer_id, w, lo, hi, handle.slot)
        self._queues.setdefault(handle.slot, []).append(req)
        self.stats.requests += 1
        if self.auto_flush and len(self._queues[handle.slot]) >= self.max_batch:
            self.flush(handle.slot)
        return req

    # --------------------------------------------------------------- flush
    def flush(self, slot: Any = None) -> List[FlushStats]:
        """Serve queued requests — one slot, or every non-empty queue."""
        slots = ([slot] if slot is not None
                 else [s for s, q in self._queues.items() if q])
        out: List[FlushStats] = []
        for s in slots:
            out.extend(self._flush_slot(s))
        return out

    def _flush_slot(self, slot) -> List[FlushStats]:
        q = self._queues.get(slot)
        if not q:
            return []
        # ONE handle snapshot for everything this flush executes: a publish
        # that lands mid-flush is observed at the next flush boundary, so a
        # batch can never mix generations (hot-swap atomicity, pinned)
        handle = self.registry.handle(slot)
        last = self._last_gen.get(slot)
        if last is not None and handle.generation != last:
            self.stats.swaps_seen += 1
        # flcheck: disable=FLC008 (one int per routed slot; slots come from the registry's fixed cluster universe, not from request traffic)
        self._last_gen[slot] = handle.generation
        out = []
        while q:
            chunk, self._queues[slot] = q[:self.max_batch], q[self.max_batch:]
            q = self._queues[slot]
            out.append(self._run_batch(handle, chunk))
        return out

    def _run_batch(self, handle: ModelHandle,
                   chunk: List[ForecastRequest]) -> FlushStats:
        n = len(chunk)
        b = bucket_for(n, self.min_bucket, self.max_batch)
        L = handle.cfg.lookback
        x = np.zeros((b, L), np.float32)
        lo = np.zeros((b, 1), np.float32)
        hi = np.ones((b, 1), np.float32)      # pad rows: scale 1, masked off
        for j, r in enumerate(chunk):
            x[j] = r.window
            lo[j, 0] = r.lo
            hi[j, 0] = r.hi
        fn = self._int8 if handle.weights == "int8" else self._fp32
        t0 = time.perf_counter()
        pred = np.asarray(fn(handle.params, jnp.asarray(x), jnp.asarray(lo),
                             jnp.asarray(hi), handle.cfg))   # blocks
        dt = time.perf_counter() - t0
        for j, r in enumerate(chunk):
            r.result = pred[j]
        self.stats.flushes += 1
        self.stats.busy_s += dt
        self.stats.by_bucket[b] = self.stats.by_bucket.get(b, 0) + 1
        return FlushStats(handle.slot, n, b, dt, handle.generation,
                          handle.weights, tuple(chunk))

    # -------------------------------------------------------------- warmup
    def warmup(self, slots=None) -> int:
        """Compile every (bucket, cfg, weights) shape the registry can
        serve; afterwards the steady state adds zero jit-cache entries
        (hot-swaps included — parameters are traced arguments).  Returns
        the number of distinct programs compiled."""
        n = 0
        seen = set()
        for s in (self.registry.slots() if slots is None else slots):
            handle = self.registry.handle(s)
            sig = (handle.cfg, handle.weights)
            if sig in seen:
                continue
            seen.add(sig)
            fn = self._int8 if handle.weights == "int8" else self._fp32
            L = handle.cfg.lookback
            for b in bucket_ladder(self.min_bucket, self.max_batch):
                fn(handle.params, jnp.asarray(np.zeros((b, L), np.float32)),
                   jnp.asarray(np.zeros((b, 1), np.float32)),
                   jnp.asarray(np.ones((b, 1), np.float32)), handle.cfg)
                n += 1
        return n
