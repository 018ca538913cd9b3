"""``correct`` comes out true on a sound run and false on the control and on
each fault a cell can have, at sizes a test run holds.  A run is driven
through ``bench/run.py``'s own ``main`` with the look for a chip skipped and
the traffic mix shrunk; the control is read from the harness's check.

The serving mix is written ahead of its cell: until ``BENCHMARK.json`` lists
``serve.gru64.steady``, its spec is read from its configuration and mix
files with its end-to-end metrics."""
import json

import jax
import numpy as np
import pytest

from bench import run as bench_run
from bench.harness import device, fl_round, serve_open_loop

TINY_TRAIN = {"meters": 16, "days": 20, "clients_per_round": 8}
TINY_SERVE = {"residents": 2000, "consumer_cache": 2000, "rate": 2000,
              "pool_meters": 64, "first_contact_share": 0.05}
SEED = 2 ** 31 + 77
SERVE_CELL = {"name": "serve.gru64.steady", "config": "gru64",
              "traffic": "serve_steady", "chips": 1}
SERVE_METRICS = [{"name": "serve_p99_ms", "unit": "ms"},
                 {"name": "serve_forecasts_per_s", "unit": "forecasts/s"},
                 {"name": "setup_s", "unit": "s"}]
LOAD_CELL = bench_run.load_cell


def load(name):
    if name != SERVE_CELL["name"]:
        return LOAD_CELL(name)
    return {"cell": SERVE_CELL,
            "model": json.loads((bench_run.BENCH / "configs" / "gru64.json")
                                .read_text()),
            "traffic": json.loads((bench_run.BENCH / "traffic"
                                   / "serve_steady.json").read_text()),
            "end_to_end": SERVE_METRICS, "per_layer": []}


@pytest.fixture
def run_cell(monkeypatch, capsys):
    """Run a cell at a tiny size through ``bench/run.py``; its result line."""
    monkeypatch.setattr(device, "require_tpu",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(device, "enable_cache", lambda: None)

    def drive(workload, tiny):
        def small(name):
            spec = load(name)
            spec["traffic"]["params"].update(tiny)
            return spec
        monkeypatch.setattr(bench_run, "load_cell", small)
        jax.clear_caches()
        assert bench_run.main(["--workload", workload, "--seed", str(SEED),
                               "--seconds", "0.5", "--trace", "0"]) == 0
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return drive


@pytest.mark.parametrize("workload,tiny", [
    ("train.lstm64.r1", TINY_TRAIN),
    ("serve.gru64.steady", TINY_SERVE)])
def test_sound_run_is_correct(run_cell, workload, tiny):
    line = run_cell(workload, tiny)
    assert line["correct"] and line["failed"] == 0
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("workload,tiny", [
    ("train.lstm64.r1", TINY_TRAIN),
    ("serve.gru64.steady", TINY_SERVE)])
def test_control_is_not_correct(workload, tiny):
    """The reference in bfloat16, in the program's place, fails a limit."""
    jax.clear_caches()
    spec = load(workload)
    traffic = spec["traffic"]
    kind = fl_round if traffic["kind"] == "fl_round" else serve_open_loop
    run = kind.Run(spec["model"]["model"],
                   {**traffic["params"], **tiny, "seconds": 0.5}, SEED)
    run.warm()
    run.window(0.5)
    run.free()
    got = kind.readings(run, "control")
    assert any(got[k] > traffic["limits"][k] for k in got)
    assert all(np.isfinite(v) and v <= traffic["limits"][k]
               for k, v in kind.readings(run, "program").items())


def test_fault_state_unchanged(run_cell, monkeypatch):
    from repro.core import server_opt
    monkeypatch.setattr(server_opt, "server_update",
                        lambda w, a, s, cfg: (w, s))
    assert not run_cell("train.lstm64.r1", TINY_TRAIN)["correct"]


def test_fault_half_batch(run_cell, monkeypatch):
    """Each SGD step takes the mean over half of its batch."""
    from repro.core import fedavg
    inner = fedavg.local_update

    def half(params, x, y, bidx, *a):
        return inner(params, x, y, bidx[:, :bidx.shape[1] // 2], *a)
    monkeypatch.setattr(fedavg, "local_update", half)
    assert not run_cell("train.lstm64.r1", TINY_TRAIN)["correct"]


def test_fault_answer_altered(run_cell, monkeypatch):
    """Each batch's first forecast is moved where the engine produces it."""
    from repro.serving import engine
    inner = engine._forecast_kwh

    def altered(params, x, lo, hi, cfg):
        out = inner(params, x, lo, hi, cfg)
        return out.at[0].add(0.05 * (hi[0] - lo[0]))
    monkeypatch.setattr(engine, "_forecast_kwh", altered)
    assert not run_cell("serve.gru64.steady", TINY_SERVE)["correct"]
