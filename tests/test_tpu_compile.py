"""Compile rehearsal for a TPU v5e chip, without the chip.

The TPU compiler is installed next to the CPU backend, and compiles for a
described (not attached) ``v5e:2x2`` topology.  These tests compile the
main path at real sizes — the Pallas recurrent cells with
``interpret=False``, the jitted federated round at the R1 cohort, the
serving forward at its largest bucket — so a kernel or a program the chip
would refuse fails here, at no chip time.  Nothing runs: a passing compile
says nothing about results or speed.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library at a time,
and every test worker imports this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import ForecasterConfig, TransformConfig
from repro.core import fedavg, losses
from repro.kernels import ops
from repro.kernels.gru_cell import gru_cell
from repro.kernels.lstm_cell import lstm_cell
from repro.models import forecaster
from repro.serving import engine as serving_engine

V5E_HBM_BYTES = 16 * 1024 ** 3
FCFG = ForecasterConfig()                       # the paper's LSTM, H = 64
R1_M, R1_STEPS, R1_BATCH = 256, 410, 64         # R1 cohort, B = 64
R1_WINDOWS = 26_280                             # a year of train windows


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no compiler logs
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits_one_chip(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < V5E_HBM_BYTES, f"{used / 2**30:.2f} GiB > 16 GiB"


@pytest.mark.parametrize("cell", ["lstm", "gru"])
@pytest.mark.parametrize("B,I", [(64, 1), (256, 1), (64, 64), (200, 1)])
def test_recurrent_cell_compiles_for_v5e(one_chip, cell, B, I):
    """Fused cell at H = 64 through ``ops._pick_block``'s tiling; B = 200
    is the batch whose block used to be unaligned (100 rows)."""
    H = FCFG.hidden_dim
    gates = 4 if cell == "lstm" else 3
    blocks = dict(block_b=ops._pick_block(B, ops._SUBLANE),
                  block_h=ops._pick_block(H, ops._LANE), interpret=False)
    f32 = jnp.float32
    x, h = _spec((B, I), f32, one_chip), _spec((B, H), f32, one_chip)
    w = (_spec((I, gates * H), f32, one_chip),
         _spec((H, gates * H), f32, one_chip),
         _spec((gates * H,), f32, one_chip))
    if cell == "lstm":
        fn = functools.partial(lstm_cell, **blocks)
        args = (x, h, h) + w
    else:
        fn = functools.partial(gru_cell, **blocks)
        args = (x, h) + w
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_r1_round_compiles_for_v5e(one_chip):
    """The jitted vmap round (local-update of 256 clients x 410 steps of
    B = 64 on a year of windows, identity transform, aggregate) fits one
    chip, and its stage scopes reach the compiled ops' metadata."""
    f32 = jnp.float32
    params = jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip),
                          forecaster.param_template(FCFG))
    L, Hz = FCFG.lookback, FCFG.horizon
    args = (params,
            _spec((R1_M, R1_WINDOWS, L, 1), f32, one_chip),
            _spec((R1_M, R1_WINDOWS, Hz), f32, one_chip),
            _spec((R1_M, R1_STEPS, R1_BATCH), jnp.int32, one_chip),
            _spec((R1_M,), f32, one_chip),
            _spec((R1_M, 2), jnp.uint32, one_chip),
            _spec((), f32, one_chip), _spec((), f32, one_chip))
    compiled = fedavg.pipeline_round.lower(
        *args, cfg=FCFG, loss=losses.make_loss("ew_mse", 2.0),
        tcfg=TransformConfig()).compile()
    _fits_one_chip(compiled)
    # the stage scopes survive the TPU compiler, where the trace reads them
    text = compiled.as_text()
    assert "/local_update/" in text and "/aggregate/" in text


def test_r1_series_round_compiles_for_v5e(one_chip):
    """The round as the training loop ships it: the cohort's normalized
    train series (y None) and the minibatch indices.  It fits one chip
    with a sixth of the window form's arguments, and the device windowing
    adds no loop to the program: no gather of the series lowers to a
    serial loop over its slices."""
    f32 = jnp.float32
    params = jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip),
                          forecaster.param_template(FCFG))
    L, Hz = FCFG.lookback, FCFG.horizon
    kw = dict(cfg=FCFG, loss=losses.make_loss("ew_mse", 2.0),
              tcfg=TransformConfig())
    rest = (_spec((R1_M, R1_STEPS, R1_BATCH), jnp.int32, one_chip),
            _spec((R1_M,), f32, one_chip),
            _spec((R1_M, 2), jnp.uint32, one_chip),
            _spec((), f32, one_chip), _spec((), f32, one_chip))
    series = fedavg.pipeline_round.lower(
        params, _spec((R1_M, R1_WINDOWS + L + Hz - 1), f32, one_chip), None,
        *rest, **kw).compile()
    windows = fedavg.pipeline_round.lower(
        params, _spec((R1_M, R1_WINDOWS, L, 1), f32, one_chip),
        _spec((R1_M, R1_WINDOWS, Hz), f32, one_chip), *rest, **kw).compile()
    _fits_one_chip(series)
    s_mem, w_mem = series.memory_analysis(), windows.memory_analysis()
    assert 6 * s_mem.argument_size_in_bytes < w_mem.argument_size_in_bytes
    assert series.as_text().count(" while(") == \
        windows.as_text().count(" while(")


@pytest.mark.parametrize("loop", ["vmap", "scan"])
def test_fused_local_update_compiles_for_v5e(one_chip, monkeypatch, loop):
    """The local update the R1 round runs on a TPU, its LSTM differentiated
    by the fused sequence kernels (``forecaster.fused_recurrence``): 256
    vmapped clients x 410 steps of B = 64 on their series, the kernels
    taking blocks of clients; or one client at a time, as the scan client
    loop runs it.  The kernels compile, and the update fits one chip."""
    from repro.core import client
    from repro.kernels import platform
    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    assert forecaster.fused_recurrence(FCFG)
    f32 = jnp.float32
    params = jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip),
                          forecaster.param_template(FCFG))
    loss = losses.make_loss("ew_mse", 2.0)
    one = lambda p, s, i, lr: client.local_update(p, s, None, i, lr, FCFG,
                                                  loss)
    T = R1_WINDOWS + FCFG.lookback + FCFG.horizon - 1
    if loop == "vmap":
        update = jax.vmap(one, in_axes=(None, 0, 0, None))
        shapes = ((R1_M, T), (R1_M, R1_STEPS, R1_BATCH))
    else:
        update = one
        shapes = ((T,), (R1_STEPS, R1_BATCH))
    jax.clear_caches()              # the path is chosen when traced
    try:
        compiled = jax.jit(update).lower(
            params, _spec(shapes[0], f32, one_chip),
            _spec(shapes[1], jnp.int32, one_chip),
            _spec((), f32, one_chip)).compile()
    finally:
        jax.clear_caches()
    assert compiled.as_text().count("tpu_custom_call") >= 2  # fwd and bwd
    _fits_one_chip(compiled)


def test_serving_forward_compiles_for_v5e(one_chip):
    """The engine's fp32 forward at its largest bucket (256 requests)."""
    f32 = jnp.float32
    params = jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip),
                          forecaster.param_template(FCFG))
    b = 256
    compiled = jax.jit(serving_engine._forecast_kwh,
                       static_argnames=("cfg",)).lower(
        params, _spec((b, FCFG.lookback), f32, one_chip),
        _spec((b, 1), f32, one_chip), _spec((b, 1), f32, one_chip),
        cfg=FCFG).compile()
    _fits_one_chip(compiled)


def test_hybrid_round_compiles_for_v5e(one_chip, monkeypatch):
    """The hybrid backbone's round as the silo cell runs it (its ten
    layers at the published widths, a look-back of 2,048, B = 4, two
    clients, which a 16 GiB chip trains one after another): the chip's
    compiler takes it, and the layer scopes reach its ops' metadata."""
    from repro.configs.base import HybridForecasterConfig
    cfg = HybridForecasterConfig()
    monkeypatch.setattr(fedavg, "_device_bytes", lambda: V5E_HBM_BYTES)
    f32 = jnp.float32
    params = jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip),
                          jax.eval_shape(cfg.init, jax.random.PRNGKey(0)))
    m, K, B = 2, 4, 4
    assert fedavg.client_loop(params, m) == "scan"
    try:
        compiled = fedavg.pipeline_round.lower(
            params, _spec((m, 26_280), f32, one_chip), None,
            _spec((m, K, B), jnp.int32, one_chip),
            _spec((m,), f32, one_chip), _spec((m, 2), jnp.uint32, one_chip),
            _spec((), f32, one_chip), _spec((), f32, one_chip),
            cfg, losses.make_loss("ew_mse", 2.0), TransformConfig()
        ).compile()
    finally:
        jax.clear_caches()
    text = compiled.as_text()
    for scope in ("/local_update/", "/hybrid/mamba/ssd/",
                  "/hybrid/attention/", "/hybrid/mlp/"):
        assert scope in text, scope
