"""The trace reduction: on hand-made planes with known answers, and on a
small trace recorded on a TPU v5 lite chip (two calls of a small federated
training run inside a ``bench.window`` span)."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import run as bench_run
from bench.harness import trace

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "v5e_fl_small.xplane.pb"
MS = 1_000_000


def _line(name, events):
    return NS(name=name, events=[NS(name=n, start_ns=s, duration_ns=d)
                                 for n, s, d in events])


def _planes():
    dev0 = NS(name="/device:TPU:0", lines=[
        _line("XLA Modules", [("jit_pipeline_round", 0, 60 * MS)]),
        _line("XLA Ops", [("fusion.1", 0, 30 * MS),
                          ("fusion.2", 10 * MS, 30 * MS),   # overlaps
                          ("all-reduce.3", 50 * MS, 10 * MS)])])
    dev1 = NS(name="/device:TPU:1", lines=[
        _line("XLA Modules", [("jit_pipeline_round", 0, 40 * MS)]),
        _line("XLA Ops", [("fusion.1", 0, 20 * MS)])])
    host = NS(name="/host:CPU", lines=[
        _line("python", [("bench.window", 0, 100 * MS),
                         ("bench.fl_call", 0, 100 * MS),
                         ("bench.round_batch", 60 * MS, 40 * MS),
                         ("jit_other", 0, 5 * MS)])])
    return [dev0, host, dev1]


def test_union_busy_collectives_and_modules():
    r = trace.reduce_planes(_planes(), window_s=0.1)
    assert r["chips"] == 2
    # chip 0 busy 0-40 and 50-60 ms = 50 ms; chip 1 busy 20 ms
    assert r["busy_s"] == pytest.approx((0.050 + 0.020) / 2)
    assert r["collective_s"] == pytest.approx(0.010 / 2)
    assert r["modules"]["jit_pipeline_round"] == pytest.approx(0.050)
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.025)


def test_idle_gaps_go_to_the_innermost_host_span():
    r = trace.reduce_planes(_planes(), window_s=0.1)
    gaps = r["breakdown"]["idle_gaps"]
    # longest: chip 1 idle 20-100 ms; bench.fl_call covers 80 ms of it,
    # bench.round_batch 40 ms: the larger overlap wins
    assert gaps[0][1] == pytest.approx(0.080)
    assert gaps[0][0] == "bench.fl_call"
    # chip 0 idle 60-100 ms lies wholly under both: the innermost wins
    g = [n for n, s in gaps if s == pytest.approx(0.040)]
    assert g == ["bench.round_batch"]


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError, match="no TPU plane"):
        trace.reduce_planes([_planes()[1]], window_s=0.1)


def test_no_device_op_in_the_window_is_an_error():
    host = NS(name="/host:CPU", lines=[
        _line("python", [("bench.window", 200 * MS, 100 * MS)])])
    with pytest.raises(ValueError, match="no device operation"):
        trace.reduce_planes([_planes()[0], host], window_s=0.1)


@pytest.mark.parametrize("metric", ["device_idle_share.train",
                                    "device_idle_share.serve"])
def test_one_idle_share_reader_serves_each_kind(metric):
    rec = {"trace": {"busy_s": 0.25}, "window_s": 1.0}
    assert bench_run.reader(metric)(rec) == pytest.approx(75.0)


def test_round_reader_without_a_round_program_is_an_error():
    rec = {"trace": {"modules": {"jit_other": 0.5}}, "rounds": 3}
    with pytest.raises(ValueError, match="no round program"):
        bench_run.reader("round_device_ms.train")(rec)


@pytest.mark.skipif(not FIXTURE.exists(), reason="fixture not recorded")
def test_recorded_trace():
    r = trace.reduce(str(FIXTURE), window_s=1.0)
    assert r["chips"] == 1
    assert 0.0 < r["busy_s"] < 10.0
    assert any("pipeline_round" in k for k in r["modules"])
    assert r["collective_s"] == 0.0
    names = {n for n, _ in r["breakdown"]["idle_gaps"]}
    assert names <= {"bench.fl_call", "unattributed"}
