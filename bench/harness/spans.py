"""Reduce the program's own spans and stage scopes in a profiler trace.

The host round loop (``core/fedavg.py::run_federated_training``) records one
``fl.round`` step span per round holding ``fl.select``, ``fl.round_batch``
(args ``clients``, ``windows``), ``fl.put`` (arg ``bytes``), ``fl.step`` and
``fl.loss_sync``.  The round program names its stages with
``jax.named_scope`` (``local_update``, ``transform``, ``aggregate``); a TPU
trace carries each operation's scope path in the ``OP_PATH`` stat of the
metadata its ``XLA Ops`` events point to, which ``xspace.event_stat`` reads
from the file (``ProfileData`` gives an event's own stats only).  Host spans
and device operations share one clock, and everything is clipped to the
benchmark's ``bench.window`` span, as in ``trace.reduce_planes``.

``reduce_planes`` gives, for each ``fl.*`` name, its count, summed self time
(duration less the union of the ``fl.*`` spans it holds on its thread) and
summed numeric args; for each stage, the device seconds as the union of its
operations (a loop and the operations inside it count once); and the
device idle seconds split by the innermost ``fl.*`` span over them.
``per_round`` turns that into per-round numbers.  Seconds of the device
are averaged over the chips.
"""
from __future__ import annotations

import numpy as np

from bench.harness import trace, xspace

SPAN = "fl."
ROUND = "fl.round"
STAGES = ("local_update", "transform", "aggregate")
OP_PATH = "tf_op"
OUTSIDE = "none"                 # idle under no fl.* span at all


def _stats(e) -> dict:
    return dict(getattr(e, "stats", ()) or ())


def _interval(e):
    return e.start_ns, e.start_ns + e.duration_ns


def _length(iv: np.ndarray) -> float:
    return float((iv[:, 1] - iv[:, 0]).sum()) * 1e-9


def _measure(u: np.ndarray, a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Length of the disjoint sorted intervals ``u`` inside each [a, b)."""
    if len(u) == 0:
        return np.zeros(len(a))
    cum = np.concatenate([[0.0], np.cumsum(u[:, 1] - u[:, 0])])

    def before(t):
        k = np.searchsorted(u[:, 0], t, side="right") - 1
        kk = np.maximum(k, 0)
        part = np.clip(t - u[kk, 0], 0.0, u[kk, 1] - u[kk, 0])
        return np.where(k < 0, 0.0, cum[kk] + part)

    return before(b) - before(a)


def _span_table(threads, t_beg, t_end) -> dict:
    """Count, self seconds and summed numeric args of each ``fl.*`` name."""
    table = {}
    for spans in threads:
        iv = np.array([(s, e) for s, e, _, _ in spans], float)
        for s, e, name, stats in spans:
            lo, hi = max(s, t_beg), min(e, t_end)
            if hi <= lo:
                continue
            inner = (iv[:, 0] >= s) & (iv[:, 1] <= e) & (
                iv[:, 1] - iv[:, 0] < e - s)
            held = trace._union(trace._clip(iv[inner], lo, hi))
            row = table.setdefault(name, {"count": 0, "self_s": 0.0,
                                          "args": {}})
            row["count"] += 1
            row["self_s"] += (hi - lo) * 1e-9 - _length(held)
            for k, v in stats.items():
                if not k.startswith("_") and isinstance(v, (int, float)):
                    row["args"][k] = row["args"].get(k, 0) + v
    return table


def _idle_by_span(gaps, spans, t_beg, t_end) -> dict:
    """Idle seconds of one chip under each innermost ``fl.*`` span (the
    shortest that covers the instant); ``OUTSIDE`` where none does."""
    cuts = np.unique(np.clip([t_beg, t_end] + [t for s, e, _, _ in spans
                                               for t in (s, e)],
                             t_beg, t_end))
    a, b = cuts[:-1], cuts[1:]
    idle = _measure(gaps, a, b)
    out = {}
    for i in np.flatnonzero(idle > 0):
        cover = [(e - s, name) for s, e, name, _ in spans
                 if s <= a[i] and e >= b[i]]
        name = min(cover)[1] if cover else OUTSIDE
        out[name] = out.get(name, 0.0) + idle[i] * 1e-9
    return out


def reduce_planes(planes, window_s: float, op_paths=None) -> dict:
    """The reduction, from ``ProfileData``-like planes (as in
    ``trace.reduce_planes``; events may carry ``stats``, (key, value)
    pairs) and, for each device plane, the scope path of each operation
    name (``xspace.event_stat``); an event's own ``OP_PATH`` stat serves
    where that has none."""
    devices, paths, threads, window = [], [], [], None
    for pl in planes:
        lines = {ln.name: ln for ln in pl.lines}
        if trace.DEVICE_PLANE.match(pl.name) and trace.OPS_LINE in lines:
            devices.append(list(lines[trace.OPS_LINE].events))
            paths.append((op_paths or {}).get(pl.name, {}))
        elif pl.name.startswith("/host:"):
            for ln in pl.lines:
                spans = []
                for e in ln.events:
                    if e.name == trace.WINDOW_SPAN and window is None:
                        window = _interval(e)
                    elif e.name.startswith(SPAN):
                        spans.append((*_interval(e), e.name, _stats(e)))
                if spans:
                    threads.append(spans)
    if not any(devices):
        raise ValueError("the trace holds no TPU plane with an "
                         f"{trace.OPS_LINE!r} line")
    if window is None:           # the window_s that end with the last op
        t_end = max(_interval(e)[1] for ops in devices for e in ops)
        window = (t_end - window_s * 1e9, t_end)
    t_beg, t_end = window
    spans = [sp for thread in threads for sp in thread]
    idle_s, by_span, stages = [], {}, {k: [] for k in STAGES}
    for ops, named in zip(devices, paths):
        iv = np.array([_interval(e) for e in ops], float).reshape(-1, 2)
        busy = trace._union(trace._clip(iv, t_beg, t_end))
        gaps = np.concatenate([[t_beg], busy.reshape(-1), [t_end]])
        gaps = gaps.reshape(-1, 2)
        gaps = gaps[gaps[:, 1] > gaps[:, 0]]
        idle_s.append(_length(gaps))
        for k, v in _idle_by_span(gaps, spans, t_beg, t_end).items():
            by_span[k] = by_span.get(k, 0.0) + v / len(devices)
        where = [named.get(e.name) or str(_stats(e).get(OP_PATH, ""))
                 for e in ops]
        for scope in STAGES:
            mine = np.array([f"/{scope}/" in p for p in where], bool)
            stages[scope].append(_length(trace._union(
                trace._clip(iv[mine], t_beg, t_end))))
    return {
        "spans": _span_table(threads, t_beg, t_end),
        "stages": {k: float(np.mean(v)) for k, v in stages.items()
                   if any(v)},
        "idle_s": float(np.mean(idle_s)),
        "idle_by_span": by_span,
        "idle_unattributed_s": by_span.get(ROUND, 0.0)
        + by_span.get(OUTSIDE, 0.0),
    }


# per-round metric: (fl.* span or stage, what of it is read, scale)
PER_ROUND = {
    "select_ms.train": ("fl.select", "self_s", 1e3),
    "window_ms.train": ("fl.round_batch", "self_s", 1e3),
    "put_ms.train": ("fl.put", "self_s", 1e3),
    "put_mb.train": ("fl.put", "bytes", 1e-6),
    "dispatch_ms.train": ("fl.step", "self_s", 1e3),
    "sync_wait_ms.train": ("fl.loss_sync", "self_s", 1e3),
    "local_update_ms.train": ("local_update", "stage", 1e3),
    "aggregate_ms.train": ("aggregate", "stage", 1e3),
}


def per_round(red: dict) -> dict:
    """Each round-loop metric per round, and the share of device idle time
    under no child span of ``fl.round`` (``idle_unattributed_share``).
    Empty for a program that records no ``fl.round``; a missing span of a
    program that does is an error, and so is a missing stage where another
    stage is there, so a rename fails the run instead of dropping the
    number.  With no stage at all (a round program compiled without its
    scopes, or served from a compile cache written before them: the
    cache's key leaves op metadata out) the stage numbers are left out."""
    sp = red["spans"]
    rounds = sp.get(ROUND, {}).get("count", 0)
    if not rounds:
        return {}
    out = {}
    for metric, (name, what, scale) in PER_ROUND.items():
        if what == "stage":
            if not red["stages"]:
                continue
            if name not in red["stages"]:
                raise ValueError(f"no device op under the scope {name!r}: "
                                 f"stages {sorted(red['stages'])}")
            v = red["stages"][name]
        elif name not in sp:
            raise ValueError(f"no {name!r} span in a traced round loop: "
                             f"spans {sorted(sp)}")
        else:
            v = sp[name]["self_s"] if what == "self_s" \
                else sp[name]["args"][what]
        out[metric] = v / rounds * scale
    idle = red["idle_s"]
    out["idle_unattributed_share.train"] = (
        red["idle_unattributed_s"] / idle * 100.0 if idle else 0.0)
    return out


def reduce(path: str, window_s: float) -> dict:
    from jax._src.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes, window_s,
                         xspace.event_stat(path, OP_PATH,
                                           trace.DEVICE_PLANE.pattern))
