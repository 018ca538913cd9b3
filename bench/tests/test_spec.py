"""``BENCHMARK.json`` names only what the harness can find: each cell's
configuration and mix file, a reader for every metric, and cells that
exist under each metric's ``workloads``."""
import json

import pytest

from bench import run as bench_run

SPEC = json.loads((bench_run.ROOT / "BENCHMARK.json").read_text())
CELLS = {w["name"]: w for w in SPEC["workloads"]}


@pytest.mark.parametrize("name", sorted(CELLS))
def test_cell_finds_its_files(name):
    spec = bench_run.load_cell(name)
    assert spec["model"]["model"] and spec["traffic"]["kind"]
    assert (bench_run.BENCH / "harness"
            / f"{spec['traffic']['kind']}.py").exists()
    assert any(m["name"] == "setup_s" for m in spec["end_to_end"])
    assert len(spec["end_to_end"]) >= 2 and spec["per_layer"]


def test_every_metric_has_a_reader_and_known_cells():
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert callable(bench_run.reader(m["name"]))
        assert set(m.get("workloads", [])) <= set(CELLS)


def test_every_configuration_is_used():
    assert {c["name"] for c in SPEC["configs"]} == {
        w["config"] for w in SPEC["workloads"]}
