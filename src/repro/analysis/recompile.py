"""Dynamic round-hot-path checks: recompile guard + implicit-transfer guard.

Two performance contracts the engine docs promise (ROADMAP "one jitted round
per execution geometry"; the semi-sync engine's event loop):

* **No per-round recompiles.**  ``pipeline_round`` is jitted with the config
  objects static — if a caller threads a value that should be traced (lr,
  round index, weights) through a static argnum instead, every round
  retraces.  :func:`count_recompiles` runs a callable for N steps and
  reports how many NEW jit cache entries each step added after the first.
* **No implicit host<->device transfers.**  The round body must consume
  device-resident arrays; a stray ``np.asarray`` on a traced value or a
  Python float materialized per round forces a sync.
  :func:`check_transfers` warms the function up (compile transfers are
  legitimate) and then re-runs it under ``jax.transfer_guard("disallow")``.

Both are *dynamic* checks (they run the function), packaged here so the CLI
can drive them against the real round bodies next to the static passes.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Optional

import jax

__all__ = ["RecompileReport", "count_recompiles", "check_transfers",
           "check_round_hot_path"]


def _live_cache_size() -> int:
    """Total live entries across the process-global pjit caches.

    Per-function caches (``jitted._cache_size()``) are the precise probe —
    pass one to :func:`count_recompiles` when you know the function under
    test.  This aggregate is the fallback for opaque step callables.
    """
    from jax._src import pjit as _pjit
    n = _pjit._cpp_pjit_cache_explicit_attributes.size()
    n += _pjit._cpp_pjit_cache_fun_only.size()
    n += _pjit._infer_params_cached.cache_info().currsize
    return int(n)


@dataclasses.dataclass
class RecompileReport:
    steps: int
    new_entries_per_step: List[int]    # cache growth AFTER the warm-up step

    @property
    def ok(self) -> bool:
        return not any(self.new_entries_per_step)

    def render(self) -> str:
        if self.ok:
            return (f"recompile guard OK: {self.steps} steps after warm-up "
                    "added 0 jit cache entries")
        return ("recompile guard FAILED: post-warm-up steps added cache "
                f"entries {self.new_entries_per_step} — a traced value is "
                "being passed as a static arg (or a new function object is "
                "created per step)")


def count_recompiles(step: Callable[[int], Any], steps: int = 3,
                     cache_size: Optional[Callable[[], int]] = None
                     ) -> RecompileReport:
    """Run ``step(i)`` for ``i in range(steps + 1)``; the first call is
    warm-up (compiles are expected), the rest must add zero cache entries.

    ``cache_size`` is the probe — pass the jitted function's own
    ``._cache_size`` for a per-function count, default is the global
    aggregate."""
    probe = cache_size or _live_cache_size
    step(0)
    growth: List[int] = []
    before = probe()
    for i in range(1, steps + 1):
        step(i)
        now = probe()
        growth.append(max(0, now - before))
        before = now
    return RecompileReport(steps, growth)


def check_transfers(step: Callable[[int], Any]) -> Optional[str]:
    """Warm ``step`` up, then re-run it with implicit transfers disallowed.
    Returns None when clean, else the transfer-guard error message."""
    step(0)
    try:
        with jax.transfer_guard("disallow"):
            out = step(1)
            jax.block_until_ready(out)
    except Exception as e:  # transfer guard raises jaxlib-level errors
        return str(e)
    return None


def check_round_hot_path(steps: int = 3):
    """Drive the REAL vmap pipeline round for a few rounds and apply both
    guards.  Returns (RecompileReport, transfer_error_or_None)."""
    import jax.numpy as jnp

    from repro.configs.base import (ForecasterConfig, SecureAggConfig,
                                    TransformConfig)
    from repro.core import fedavg, losses
    from repro.models.forecaster import init_forecaster

    fcfg = ForecasterConfig(hidden_dim=8)
    tcfg = TransformConfig(clip_norm=1.0, noise_multiplier=0.5,
                           quantize_bits=4)
    scfg = SecureAggConfig(enabled=False)
    loss = losses.make_loss("mse")
    m, n_win, steps_l, batch = 4, 4, 2, 2

    root = jax.random.PRNGKey(1234)  # flcheck: disable=FLC001 (self-contained guard harness; no config seed exists here)
    params = init_forecaster(jax.random.fold_in(root, 0), fcfg)
    x = jnp.zeros((m, n_win, fcfg.lookback, 1), jnp.float32)
    y = jnp.zeros((m, n_win, fcfg.horizon), jnp.float32)
    bidx = jnp.zeros((m, steps_l, batch), jnp.int32)
    w = jnp.ones((m,), jnp.float32)
    lr = jnp.float32(0.01)
    mu = jnp.float32(0.0)
    # per-round keys precomputed ON DEVICE: the harness itself must not
    # trip the transfer guard it is applying to the round body
    all_keys = jax.vmap(lambda i: jax.random.fold_in(root, i))(
        jnp.arange((steps + 1) * m)).astype(jnp.uint32)
    round_keys = [jax.block_until_ready(all_keys[i * m:(i + 1) * m])
                  for i in range(steps + 1)]

    def step(i: int):
        # per-round key refresh + traced lr: exactly what the engine does
        out = fedavg.pipeline_round(params, x, y, bidx, w, round_keys[i],
                                    lr, mu, fcfg, loss, tcfg, scfg, None)
        return out[0]

    report = count_recompiles(step, steps=steps,
                              cache_size=fedavg.pipeline_round._cache_size)
    transfer_err = check_transfers(step)
    return report, transfer_err
