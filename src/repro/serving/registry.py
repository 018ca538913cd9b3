"""Serving model registry: per-slot model handles with atomic hot-swap.

The serving tier's source of truth for "which parameters answer this
request".  A **slot** is any hashable routing key — the cluster ids the
:mod:`repro.serving.router` produces (``GLOBAL_SLOT = -1`` is the single
global model, matching the FL driver's cluster id for unclustered runs), or
richer keys like ``("CA", 2)`` for per-state deployments.  Each slot holds an
immutable :class:`ModelHandle`; :meth:`ModelRegistry.publish` builds the
replacement handle COMPLETELY (device transfer, int8 quantization) before the
swap, and the swap itself is one dict assignment under a lock — so a reader
either sees the old generation or the new one, never a half-built mix, and an
in-flight batch that snapshotted its handle finishes on the old parameters.

Generations are strictly monotone per slot: a stale publish (generation ≤
the live one) raises, or is skipped with ``if_newer=True`` — the polling
path, where several pollers may race on the same checkpoint glob.

**int8 serving weights** (``weights="int8"``) store each leaf as an int8
integer grid plus one fp32 scale — a 4× parameter-memory cut — using
EXACTLY the stochastic-rounding grid of the training-side uplink quantizer
(:class:`repro.core.transforms.StochasticQuantize`): per-leaf max-abs
scaling, ``floor(x/s + u)`` rounding.  ``dequantize_params(quantize_params
(p, key))`` is bit-identical to ``StochasticQuantize(8)(p, key)``, pinned by
``tests/test_serving.py``, and the fp32-vs-int8 serving MAPE delta is pinned
there too.

**FL rounds as publishers**: a training run with ``checkpoint_path`` becomes
a publisher — :meth:`ModelRegistry.poll_checkpoint` watches a checkpoint
glob via :func:`repro.checkpoint.latest` (metadata-only reads, no array
traffic) and republishes every per-cluster slot whose generation advanced.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro import checkpoint
from repro.configs.base import ForecasterConfig

__all__ = ["GLOBAL_SLOT", "ModelHandle", "ModelRegistry",
           "quantize_params", "dequantize_params"]

# the FL driver reports the unclustered run as cluster id -1; the serving
# tier reuses it as the fallback slot, so checkpoint polling needs no remap
GLOBAL_SLOT = -1

_WEIGHT_KINDS = ("fp32", "int8")


def _is_qleaf(node: Any) -> bool:
    return isinstance(node, dict) and set(node.keys()) == {"q", "scale"}


def quantize_params(params, key: jax.Array, bits: int = 8):
    """fp32 param pytree -> tree of ``{"q": int8, "scale": fp32}`` leaves.

    Same grid + stochastic rounding as the uplink quantizer
    (``transforms.StochasticQuantize``): per-leaf max-abs scale to the
    signed ``2^(bits-1)-1`` grid, unbiased ``floor(x/s + u)`` rounding,
    per-leaf keys split exactly as the transform stack splits them — so
    ``dequantize_params(quantize_params(p, key))`` reproduces
    ``StochasticQuantize(bits)(p, key)`` bit-for-bit (regression-pinned).
    Unlike the transform (which simulates the wire and returns floats),
    the integer grid is MATERIALIZED here: serving holds 1 byte/param.
    """
    levels = float(2 ** (bits - 1) - 1)
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(key, len(leaves))
    out = []
    for x, k in zip(leaves, keys):
        x = jnp.asarray(x, jnp.float32)
        scale = jnp.max(jnp.abs(x)) / levels
        safe = jnp.maximum(scale, jnp.finfo(jnp.float32).tiny)
        u = jax.random.uniform(k, x.shape)
        q = jnp.clip(jnp.floor(x / safe + u), -levels, levels)
        out.append({"q": q.astype(jnp.int8), "scale": safe})
    return jax.tree.unflatten(treedef, out)


def dequantize_params(qparams):
    """int8 q-leaf tree -> fp32 param pytree (``q * scale`` per leaf).

    jit-safe: the serving engine calls this INSIDE its jitted forward, so
    the dequantized fp32 copy is an XLA temporary, never host memory.
    """
    return jax.tree.map(
        lambda n: n["q"].astype(jnp.float32) * n["scale"],
        qparams, is_leaf=_is_qleaf)


@dataclasses.dataclass(frozen=True)
class ModelHandle:
    """One immutable serving model: parameters + config + generation.

    Handles are what the batching engine snapshots at flush time — frozen,
    so a hot-swap can never mutate parameters under an in-flight batch.
    ``params`` is an fp32 pytree (``weights="fp32"``) or a q-leaf tree
    (``weights="int8"``, see :func:`quantize_params`).
    """
    slot: Any
    cfg: ForecasterConfig
    params: Any
    weights: str
    generation: int


class ModelRegistry:
    """Slot -> :class:`ModelHandle` map with atomic, monotone hot-swap."""

    def __init__(self):
        self._slots: Dict[Any, ModelHandle] = {}
        self._lock = threading.Lock()
        # per-glob watermark: poll_checkpoint re-reads arrays only when the
        # (metadata-only) generation probe says something advanced
        self._poll_gen: Dict[str, int] = {}

    # ------------------------------------------------------------ publish
    def publish(self, params, cfg: ForecasterConfig, *, slot: Any = GLOBAL_SLOT,
                generation: int = 0, weights: str = "fp32",
                key: Optional[jax.Array] = None,
                if_newer: bool = False) -> Optional[ModelHandle]:
        """Build a fresh handle and atomically swap it into ``slot``.

        The handle is built COMPLETELY before the swap (device transfer,
        int8 quantization), so readers never observe intermediate state;
        in-flight batches keep the handle they snapshotted.  Generations
        are strictly monotone per slot: a stale ``generation`` raises
        ``ValueError``, or returns ``None`` with ``if_newer=True`` (the
        poller idiom).  ``weights="int8"`` requires ``key`` (stochastic
        rounding; fold it from a config seed, never a literal).
        """
        if weights not in _WEIGHT_KINDS:
            raise ValueError(f"weights={weights!r}; pick from {_WEIGHT_KINDS}")
        if weights == "int8":
            if key is None:
                raise ValueError("int8 publish needs a PRNG key for "
                                 "stochastic rounding (derive from the "
                                 "config seed)")
            stored = quantize_params(params, key)
        else:
            stored = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32),
                                  params)
        handle = ModelHandle(slot=slot, cfg=cfg, params=stored,
                             weights=weights, generation=int(generation))
        with self._lock:
            cur = self._slots.get(slot)
            if cur is not None and handle.generation <= cur.generation:
                if if_newer:
                    return None
                raise ValueError(
                    f"stale publish for slot {slot!r}: generation "
                    f"{handle.generation} <= live {cur.generation}")
            # flcheck: disable=FLC008 (slot universe = cluster ids from the FL config, fixed per deployment; hot-swap REPLACES handles, never adds keys past the cluster count)
            self._slots[slot] = handle
        return handle

    # ------------------------------------------------------------- lookup
    def handle(self, slot: Any = GLOBAL_SLOT) -> ModelHandle:
        """The live handle for ``slot``, falling back to ``GLOBAL_SLOT``
        when the slot has no model (e.g. clustering is on but this cluster
        was never published) — the router's documented fallback."""
        with self._lock:
            h = self._slots.get(slot)
            if h is None:
                h = self._slots.get(GLOBAL_SLOT)
        if h is None:
            raise KeyError(
                f"no model for slot {slot!r} and no {GLOBAL_SLOT} global "
                "fallback — publish one first")
        return h

    def slots(self) -> List[Any]:
        with self._lock:
            return sorted(self._slots, key=repr)

    def generation(self, slot: Any = GLOBAL_SLOT) -> int:
        """Live generation of ``slot`` (no fallback), -1 when empty."""
        with self._lock:
            h = self._slots.get(slot)
        return -1 if h is None else h.generation

    # ------------------------------------------------- checkpoint polling
    def poll_checkpoint(self, path_glob, cfg: ForecasterConfig, *,
                        weights: str = "fp32",
                        key: Optional[jax.Array] = None) -> List[ModelHandle]:
        """Publish new globals from the freshest checkpoint under a glob.

        ``repro.checkpoint.latest`` finds the highest-generation match with
        metadata-only reads; arrays are loaded only when that generation
        beats this registry's per-glob watermark.  FL-driver checkpoints
        publish every finished cluster (``done/<cid>/params``) plus the
        in-progress one (``cur/params`` under ``metadata["cluster"]``);
        a bare param-tree checkpoint publishes ``GLOBAL_SLOT``.  Returns
        the handles actually swapped in (stale slots are skipped).
        """
        found = checkpoint.latest(path_glob)
        if found is None:
            return []
        path, gen = found
        # watermark read under the lock: two concurrent pollers must not
        # both see a stale watermark and double-load the same arrays
        with self._lock:
            if gen <= self._poll_gen.get(str(path_glob), -1):
                return []
        flat, meta = checkpoint.load_arrays(path)
        meta = meta or {}
        template = cfg.param_template()
        entries = [(int(cid), f"done/{cid}/params/")
                   for cid in meta.get("done", [])]
        if "cluster" in meta:
            entries.append((int(meta["cluster"]), "cur/params/"))
        if not entries:                     # plain params-tree checkpoint
            entries.append((GLOBAL_SLOT, ""))
        updated = []
        for slot, prefix in entries:
            try:
                params = checkpoint.unflatten_like(template, flat,
                                                   prefix=prefix)
            except KeyError:
                continue                    # slot absent from this snapshot
            # +1 keeps GLOBAL_SLOT=-1 and slot 0 on distinct key streams
            k = None if key is None else jax.random.fold_in(key, slot + 1)
            h = self.publish(params, cfg, slot=slot, generation=gen,
                             weights=weights, key=k, if_newer=True)
            if h is not None:
                updated.append(h)
        # watermark write back under the lock (NOT held across publish():
        # publish takes the same non-reentrant lock).  Worst case two racing
        # pollers both pass the read above and both publish — if_newer makes
        # the second a no-op, and max() keeps the watermark monotone.
        with self._lock:
            prev = self._poll_gen.get(str(path_glob), -1)
            # flcheck: disable=FLC008 (one watermark per polled glob pattern; the glob set is static config, not per-request traffic)
            self._poll_gen[str(path_glob)] = max(gen, prev)
        return updated
