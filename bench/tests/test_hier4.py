"""The ``train.lstm64.hier4`` mix on four virtual CPU devices: the
hierarchical round on the 2x2 (region, clients) mesh agrees with the flat
float32 reference within ``fl_r1``'s limits, and a planted fault that
leaves out the edge -> region -> cloud psum pair fails ``loss_gap``.

Runs in a child process: the device count is fixed when JAX starts."""
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

CHILD = r"""
import json, sys
import jax
from bench.harness import fl_round
from repro.core import aggregation
traffic = json.load(open("bench/traffic/fl_r1_hier.json"))
p = dict(traffic["params"], meters=16, days=20, clients_per_round=8,
         batch_size=32, rounds_per_call=2)
assert p["aggregation"] == "hierarchical" and len(jax.devices()) == 4
out = {}
for fault in ("", "no_psum_pair"):
    if fault:
        aggregation.HierarchicalAggregator.reduce = lambda self, x: x
        jax.clear_caches()
    run = fl_round.Run({"cell": "lstm", "input_dim": 1, "hidden_dim": 64,
                        "n_layers": 1, "lookback": 8, "horizon": 4},
                       p, 3100000007)
    run.warm()
    out[fault or "program"] = fl_round.readings(run, "program")
print(json.dumps(out))
"""


def test_hierarchical_round_matches_flat_reference_and_fault_fails():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    res = subprocess.run([sys.executable, "-c", CHILD], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    got = json.loads(res.stdout.strip().splitlines()[-1])
    limits = json.loads((ROOT / "bench/traffic/fl_r1_hier.json")
                        .read_text())["limits"]
    assert all(got["program"][k] <= limits[k] for k in limits), got
    assert got["no_psum_pair"]["loss_gap"] > limits["loss_gap"], got
