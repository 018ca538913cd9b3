"""Device check, compile cache and compile counting for one benchmark run.

The benchmark measures the chip and nothing else: ``require_tpu`` stops a
run before any work when JAX's default backend is not a TPU, or holds fewer
chips than the cell asks for.  The compile cache is the program's own fixed
directory (``launch/compile_cache.py``); the harness also lowers JAX's
one-second floor for writing an entry to zero, so the many sub-second
programs of a round are cached too.
"""
from __future__ import annotations

import jax

_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE_EVENTS = (_LOWER_EVENT, "/jax/core/compile/backend_compile_duration")


class NoChip(RuntimeError):
    """The run was started where the cell's chips are not."""


def require_tpu(chips: int) -> list:
    """The first ``chips`` TPU devices, or :class:`NoChip`."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX's default backend is {devs[0].platform!r}, not a "
                     "TPU: this benchmark measures the chip and never falls "
                     "back to another backend")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def enable_cache() -> str:
    """The program's persistent compile cache, every entry written."""
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileMeter:
    """Seconds JAX spent lowering and compiling (or loading from the
    persistent cache), the lowerings it made, and the cache's hits."""

    def __init__(self):
        self.seconds = 0.0
        self.lowerings = 0
        self.cache_requests = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration
        if event == _LOWER_EVENT:
            self.lowerings += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.cache_requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def device_block(devs) -> dict:
    """What the result line says of the devices: platform, kind, count and
    the peak bytes in use on the fullest chip."""
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": max(peaks)}
