"""Reduce a profiler trace (``.xplane.pb``) to device busy time, per-program
device time, collective time and a short breakdown.

Each TPU is one plane, ``/device:TPU:<n>``.  Its ``XLA Ops`` line holds one
event per operation executed, its ``XLA Modules`` line one per program run.
Busy time is the union of the operation intervals inside the window, so
overlapping operations count once.  Times are averaged over the chips.
Host spans that the benchmark itself records around its calls
(``jax.profiler.TraceAnnotation`` named ``bench.*``) sit on the host plane,
on the same clock; each idle gap of a device is attributed to the host span
that covers most of it.
"""
from __future__ import annotations

import glob
import os
import re

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|psum", re.I)
HOST_SPAN = "bench."
WINDOW_SPAN = "bench.window"
TOP = 10


def find(tdir: str) -> str:
    files = sorted(glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {tdir}")
    return files[-1]


def _events(line):
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events]


def _union(iv: np.ndarray) -> np.ndarray:
    """Merged, sorted (start, end) intervals of an (n, 2) array."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.empty(len(iv), bool)
    new[0] = True
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    idx = np.flatnonzero(new)
    stops = np.maximum.reduceat(iv[:, 1], idx)
    return np.stack([starts, stops], axis=1)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def reduce_planes(planes, window_s: float) -> dict:
    """The reduction, from ``ProfileData``-like planes (each with ``name``
    and ``lines``; a line with ``name`` and ``events`` of ``name``,
    ``start_ns``, ``duration_ns``)."""
    devices, host = [], []
    for pl in planes:
        lines = {ln.name: ln for ln in pl.lines}
        if DEVICE_PLANE.match(pl.name) and OPS_LINE in lines:
            devices.append((pl.name, _events(lines[OPS_LINE]),
                            _events(lines[MODULES_LINE])
                            if MODULES_LINE in lines else []))
        elif pl.name.startswith("/host:"):
            for ln in pl.lines:
                host += [e for e in _events(ln) if e[2].startswith(HOST_SPAN)]
    if not devices:
        raise ValueError("the trace holds no TPU plane with an "
                         f"{OPS_LINE!r} line: planes "
                         f"{sorted(pl.name for pl in planes)}")
    # the window: the benchmark's own span around it, else the window_s
    # that end where the last operation ends
    span = [e for e in host if e[2] == WINDOW_SPAN]
    if span:
        t_beg, t_end = span[0][0], span[0][1]
    else:
        t_end = max(max(e[1] for e in ops) for _, ops, _ in devices if ops)
        t_beg = t_end - window_s * 1e9
    host = [e for e in host if e[2] != WINDOW_SPAN]
    busy, collective, modules, op_time, gaps = [], [], {}, {}, []
    host_iv = np.array([(s, e) for s, e, _ in host], float).reshape(-1, 2)
    host_names = [n for _, _, n in host]
    for _, ops, mods in devices:
        iv = _clip(np.array([(s, e) for s, e, _ in ops], float).reshape(-1, 2),
                   t_beg, t_end)
        u = _union(iv)
        busy.append(float((u[:, 1] - u[:, 0]).sum()) * 1e-9)
        c = 0.0
        for s, e, name in ops:
            if e <= t_beg or s >= t_end:
                continue
            d = (min(e, t_end) - max(s, t_beg)) * 1e-9
            op_time[name] = op_time.get(name, 0.0) + d
            if COLLECTIVE.search(name):
                c += d
        collective.append(c)
        for s, e, name in mods:
            if e > t_beg and s < t_end:
                d = (min(e, t_end) - max(s, t_beg)) * 1e-9
                modules[name] = modules.get(name, 0.0) + d
        # idle gaps between merged busy intervals, inside the window
        edges = np.concatenate([[t_beg], u.reshape(-1), [t_end]])
        for g0, g1 in edges.reshape(-1, 2):
            if g1 > g0:
                gaps.append((g0, g1))
    n = len(devices)
    if not any(busy):
        raise ValueError("no device operation ran inside the traced window")
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "chips": n,
        "busy_s": float(np.mean(busy)),
        "collective_s": float(np.mean(collective)),
        "modules": {k: v / n for k, v in modules.items()},
        "breakdown": {
            "device_ops": [[k, v / n] for k, v in sorted(
                op_time.items(), key=lambda kv: -kv[1])[:TOP]],
            "idle_gaps": [[_attribute(g, host_iv, host_names),
                           (g[1] - g[0]) * 1e-9] for g in gaps[:TOP]],
        },
    }


def _attribute(gap, host_iv: np.ndarray, names: list) -> str:
    """The innermost benchmark host span covering most of the gap."""
    if len(host_iv) == 0:
        return "unattributed"
    ov = (np.minimum(host_iv[:, 1], gap[1])
          - np.maximum(host_iv[:, 0], gap[0]))
    best = np.flatnonzero(ov > 0)
    if len(best) == 0:
        return "unattributed"
    # most overlap first, then the shortest (innermost) span
    k = min(best, key=lambda i: (-ov[i], host_iv[i, 1] - host_iv[i, 0]))
    return names[k]


def reduce(path: str, window_s: float) -> dict:
    from jax._src.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes, window_s)
