"""The reduction of the program's spans and stage scopes: on hand-made
planes with known answers, and on a small trace recorded on a TPU v5 lite
chip (``bench/tools/trace_round.py --seed 7 --seconds 0 --python-tracer 0
--set meters=16 --set days=4 --set clients_per_round=4 --set
rounds_per_call=2``: one call of two rounds inside a ``bench.window``
span)."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench.harness import spans, trace, xspace

FIXTURE = (Path(__file__).resolve().parent / "fixtures"
           / "v5e_fl_small.xplane.pb")
MS = 1_000_000
ROUND_CHILDREN = ("fl.select", "fl.round_batch", "fl.put", "fl.step",
                  "fl.loss_sync")


def _line(name, events):
    """Events as (name, start ms, duration ms[, stats dict])."""
    return NS(name=name, events=[
        NS(name=e[0], start_ns=e[1] * MS, duration_ns=e[2] * MS,
           stats=list((e[3] if len(e) > 3 else {}).items()))
        for e in events])


def _round(t0, op_path="jit(pipeline_round)"):
    """One 100-ms round at ``t0`` ms: host spans, and device operations
    busy 0-60 (a loop holding a fusion), 70-80 and 85-90 ms of it."""
    host = [("fl.round", t0, 100, {"_r": 1, "step_num": t0 // 100}),
            ("fl.select", t0, 10),
            ("fl.round_batch", t0 + 10, 30, {"clients": 4, "windows": 90}),
            ("bench.round_batch", t0 + 12, 26),
            ("fl.put", t0 + 40, 10, {"bytes": 3_000_000}),
            ("fl.step", t0 + 50, 10),
            ("fl.loss_sync", t0 + 60, 35)]
    ops = [("while.1", t0, 60, {"tf_op": f"{op_path}/local_update/while"}),
           ("fusion.2", t0 + 10, 20,
            {"tf_op": f"{op_path}/local_update/while/body/dot_general"}),
           ("fusion.3", t0 + 70, 10, {"tf_op": f"{op_path}/aggregate/add"}),
           ("copy.4", t0 + 85, 5, {"tf_op": "jit(multiply)/mul"})]
    return host, ops


def _planes(rounds=2, window=(0, 250), drop=()):
    host, ops = [("bench.window", window[0], window[1] - window[0])], []
    for k in range(rounds):
        h, o = _round(100 * k)
        host += [e for e in h if e[0] not in drop]
        ops += o
    return [NS(name="/device:TPU:0", lines=[_line("XLA Ops", ops)]),
            NS(name="/host:CPU", lines=[_line("python", host),
                                        _line("other", [("fl.x", 0, 1)])])]


def test_self_time_leaves_out_child_spans():
    r = spans.reduce_planes(_planes(), window_s=0.25)["spans"]
    assert r["fl.round"]["count"] == 2
    # 100 ms less the children's union (0-95 ms) = 5 ms per round
    assert r["fl.round"]["self_s"] == pytest.approx(2 * 0.005)
    assert r["fl.loss_sync"]["self_s"] == pytest.approx(2 * 0.035)
    # bench.* spans are the benchmark's, never a child of a program span
    assert r["fl.round_batch"]["self_s"] == pytest.approx(2 * 0.030)
    assert "bench.round_batch" not in r
    # a span on another thread is counted on its own
    assert r["fl.x"]["self_s"] == pytest.approx(0.001)


def test_span_args_are_summed_and_internal_stats_left_out():
    r = spans.reduce_planes(_planes(), window_s=0.25)["spans"]
    assert r["fl.put"]["args"] == {"bytes": 6_000_000}
    assert r["fl.round_batch"]["args"] == {"clients": 8, "windows": 180}
    assert "_r" not in r["fl.round"]["args"]


def test_spans_are_clipped_to_the_window():
    r = spans.reduce_planes(_planes(window=(0, 180)), window_s=0.25)
    sp = r["spans"]
    # the second round's loss_sync (160-195 ms) keeps 20 ms of its 35
    assert sp["fl.loss_sync"]["self_s"] == pytest.approx(0.035 + 0.020)
    assert sp["fl.round"]["count"] == 2


def test_stage_time_is_the_union_of_its_nested_operations():
    st = spans.reduce_planes(_planes(), window_s=0.25)["stages"]
    # the fusion inside the loop counts once: 60 ms per round, not 80
    assert st["local_update"] == pytest.approx(2 * 0.060)
    assert st["aggregate"] == pytest.approx(2 * 0.010)
    assert "transform" not in st


def test_stage_time_is_averaged_over_chips():
    planes = _planes()
    _, ops = _round(0)
    planes.append(NS(name="/device:TPU:1", lines=[_line("XLA Ops", ops)]))
    st = spans.reduce_planes(planes, window_s=0.25)["stages"]
    assert st["local_update"] == pytest.approx((0.120 + 0.060) / 2)


def test_idle_time_goes_to_the_innermost_span_over_it():
    r = spans.reduce_planes(_planes(), window_s=0.25)
    # per round idle 60-70, 80-85, 90-100 ms; then 200-250 ms, under no span
    assert r["idle_s"] == pytest.approx(2 * 0.025 + 0.050)
    by = r["idle_by_span"]
    assert by["fl.loss_sync"] == pytest.approx(2 * 0.020)
    assert by["fl.round"] == pytest.approx(2 * 0.005)
    assert by[spans.OUTSIDE] == pytest.approx(0.050)
    assert sum(by.values()) == pytest.approx(r["idle_s"])
    assert r["idle_unattributed_s"] == pytest.approx(2 * 0.005 + 0.050)


def test_per_round_numbers():
    got = spans.per_round(spans.reduce_planes(_planes(window=(0, 200)),
                                              window_s=0.2))
    assert got["select_ms.train"] == pytest.approx(10.0)
    assert got["window_ms.train"] == pytest.approx(30.0)
    assert got["put_ms.train"] == pytest.approx(10.0)
    assert got["put_mb.train"] == pytest.approx(3.0)
    assert got["dispatch_ms.train"] == pytest.approx(10.0)
    assert got["sync_wait_ms.train"] == pytest.approx(35.0)
    assert got["local_update_ms.train"] == pytest.approx(60.0)
    assert got["aggregate_ms.train"] == pytest.approx(10.0)
    # idle 25 ms a round, 5 of it under fl.round alone
    assert got["idle_unattributed_share.train"] == pytest.approx(20.0)


@pytest.mark.parametrize("missing", ROUND_CHILDREN)
def test_per_round_without_a_span_is_an_error(missing):
    red = spans.reduce_planes(_planes(drop=(missing,)), window_s=0.25)
    with pytest.raises(ValueError, match=f"no {missing!r} span"):
        spans.per_round(red)


@pytest.mark.parametrize("scope", ["local_update", "aggregate"])
def test_per_round_without_one_stage_is_an_error(scope):
    red = spans.reduce_planes(_planes(), window_s=0.25)
    del red["stages"][scope]
    with pytest.raises(ValueError, match=f"scope {scope!r}"):
        spans.per_round(red)


def test_per_round_of_a_round_program_without_scopes_leaves_stages_out():
    red = spans.reduce_planes(_planes(), window_s=0.25)
    red["stages"] = {}
    got = spans.per_round(red)
    assert "local_update_ms.train" not in got
    assert "aggregate_ms.train" not in got
    assert got["select_ms.train"] == pytest.approx(10.0)


def test_per_round_of_a_program_without_spans_is_empty():
    red = spans.reduce_planes(_planes(drop=("fl.round",) + ROUND_CHILDREN),
                              window_s=0.25)
    assert spans.per_round(red) == {}


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError, match="no TPU plane"):
        spans.reduce_planes(_planes()[1:], window_s=0.25)


@pytest.fixture(scope="module")
def recorded():
    if not FIXTURE.exists():
        pytest.skip("fixture not recorded")
    from jax._src.profiler import ProfileData
    return ProfileData.from_file(str(FIXTURE))


def test_recorded_trace_keeps_scope_paths_in_event_metadata(recorded):
    paths = xspace.event_stat(str(FIXTURE), spans.OP_PATH,
                              trace.DEVICE_PLANE.pattern)
    ops = paths["/device:TPU:0"]
    # ProfileData's own stats of an op event carry no path
    dev, = [pl for pl in recorded.planes if pl.name == "/device:TPU:0"]
    line, = [ln for ln in dev.lines if ln.name == trace.OPS_LINE]
    assert all(k != spans.OP_PATH for e in line.events for k, _ in e.stats)
    for scope in ("local_update", "aggregate"):
        assert any(v.startswith("jit(pipeline_round)/" + scope + "/")
                   for v in ops.values()), scope


def test_recorded_trace_has_every_span_and_the_stages(recorded):
    red = spans.reduce(str(FIXTURE), window_s=1.0)
    rounds = red["spans"]["fl.round"]["count"]
    assert rounds >= 2
    for name in ROUND_CHILDREN:
        assert red["spans"][name]["count"] == rounds, name
    assert red["stages"]["local_update"] > 0
    assert red["stages"]["aggregate"] > 0
    got = spans.per_round(red)
    assert got["put_mb.train"] > 0
    assert sum(red["idle_by_span"].values()) == pytest.approx(red["idle_s"])


def test_recorded_loss_sync_ends_just_after_its_round_program(recorded):
    """The host spans share the device's clock: each ``fl.loss_sync`` ends
    no earlier than the round program it waits on, and within 5 ms."""
    host = [e for pl in recorded.planes if pl.name.startswith("/host:")
            for ln in pl.lines for e in ln.events]
    mods = [e for pl in recorded.planes if pl.name.startswith("/device:TPU:")
            for ln in pl.lines if ln.name == "XLA Modules"
            for e in ln.events if "pipeline_round" in e.name]
    rounds = [e for e in host if e.name == "fl.round"]
    syncs = [e for e in host if e.name == "fl.loss_sync"]
    assert len(syncs) == len(rounds) >= 2
    for r in rounds:
        end = r.start_ns + r.duration_ns
        sync, = [e for e in syncs if r.start_ns <= e.start_ns < end]
        mod = max((m for m in mods if r.start_ns <= m.start_ns < end),
                  key=lambda m: m.start_ns)
        lag = (sync.start_ns + sync.duration_ns
               - (mod.start_ns + mod.duration_ns))
        assert 0 <= lag <= 5 * MS, lag


def _pb(field, value):
    """One protobuf field: a varint for an int, else length-delimited."""
    def varint(n):
        out = bytearray()
        while True:
            out.append(n & 0x7F | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not out[-1] & 0x80:
                return bytes(out)
    if isinstance(value, int):
        return varint(field << 3) + varint(value)
    data = value.encode() if isinstance(value, str) else value
    return varint(field << 3 | 2) + varint(len(data)) + data


def _entry(key, value):
    return _pb(1, key) + _pb(2, value)


def test_event_stat_reads_inline_and_interned_strings(tmp_path):
    def stat_meta(i, name):
        return _pb(5, _entry(i, _pb(1, i) + _pb(2, name)))

    def event(i, name, stat):
        return _pb(4, _entry(i, _pb(1, i) + _pb(2, name) + _pb(5, stat)))

    device = _pb(2, "/device:TPU:0") + b"".join([
        stat_meta(1, "tf_op"), stat_meta(2, "jit(f)/aggregate/add"),
        stat_meta(3, "flops"),
        event(1, "%fusion.1", _pb(1, 1) + _pb(5, "jit(f)/local_update/dot")),
        event(2, "%add.2", _pb(1, 1) + _pb(7, 2)),          # interned
        event(3, "%copy.3", _pb(1, 3) + _pb(4, 7)),         # another stat
        event(4, "%dup", _pb(1, 1) + _pb(5, "jit(f)/local_update/x")),
        event(5, "%dup", _pb(1, 1) + _pb(5, "jit(g)/y")),   # disagrees
    ])
    host = _pb(2, "/host:CPU") + event(1, "%fusion.1",
                                       _pb(1, 1) + _pb(5, "no"))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_pb(1, device) + _pb(1, host))
    got = xspace.event_stat(str(path), "tf_op", trace.DEVICE_PLANE.pattern)
    assert got == {"/device:TPU:0": {"%fusion.1": "jit(f)/local_update/dot",
                                     "%add.2": "jit(f)/aggregate/add",
                                     "%dup": ""}}
