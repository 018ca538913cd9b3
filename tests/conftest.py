"""Test session setup.

Locks the jax backend BEFORE any test module can import something that
fiddles with XLA_FLAGS mid-session (the dry-run launcher sets
--xla_force_host_platform_device_count=512 for itself; tests must never pick
that up after the fact).  The device count itself comes from the
environment: plain ``pytest`` runs single-device, while ``./test.sh``
exports ``--xla_force_host_platform_device_count=8`` up front so the
multi-device shard_map tests run on real (virtual) meshes.
"""
import importlib.util
import pathlib

import jax

jax.devices()                                            # lock backend now

import numpy as np  # noqa: E402
import pytest  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def reference_losses():
    """``losses(series, fcfg, flcfg)``: the round losses of the plain
    FedAvg reference, ``bench/references/forecaster.py::fedavg_rounds``
    (loaded from its file; it imports nothing of the system), for a
    ``run_federated_training`` run of plain FedAvg: no clustering, no
    holdout, uniform weights."""
    spec = importlib.util.spec_from_file_location(
        "reference_forecaster", ROOT / "bench/references/forecaster.py")
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)

    def losses(series, fcfg, flcfg):
        cfg = {k: getattr(fcfg, k) for k in (
            "cell", "input_dim", "hidden_dim", "n_layers", "lookback",
            "horizon")}
        fl = {"clients_per_round": flcfg.clients_per_round,
              "batch_size": flcfg.batch_size, "lr": flcfg.lr,
              "beta": flcfg.beta,
              "train_frac": 0.75}          # the window provider's split
        _, out = ref.fedavg_rounds(np.asarray(series), flcfg.seed, cfg, fl,
                                   flcfg.rounds)
        return np.asarray(out, np.float64)
    return losses
