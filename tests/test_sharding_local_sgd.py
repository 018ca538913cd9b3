"""Sharding rules, local-SGD/DiLoCo semantics, cost model, SARIMA."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import local_sgd, sarima
from repro.data import synthetic
from repro.launch import costmodel
from repro.sharding import ShardingRules, constrain, use_rules
from repro.sharding.rules import safe_spec


# ------------------------------------------------------------- rules
def test_safe_spec_drops_indivisible_axes():
    mesh = jax.make_mesh((1,), ("model",))
    # single-device axes (size 1) always pass through
    assert safe_spec((56, 64), P("model", None), mesh) == P("model", None)


def test_constrain_noop_without_rules():
    x = jnp.ones((4, 4))
    assert constrain(x, "batch", None) is x


def test_param_pspec_rules():
    mesh = jax.make_mesh((1,), ("data",))
    rules = ShardingRules(mesh, tensor_axis="model", fsdp_axis="data")
    assert rules.param_pspec(("blocks", "attn", "wq"), (1024, 2048)) == \
        P("data", "model")
    assert rules.param_pspec(("blocks", "attn", "wo"), (2048, 1024)) == \
        P("model", "data")
    # stacked layer axis is never sharded
    assert rules.param_pspec(("blocks", "moe", "moe_w_in"),
                             (24, 16, 512, 128)) == \
        P(None, "model", "data", None)
    assert rules.param_pspec(("final_norm",), (1024,)) == P(None)


def test_shard_batch_off_disables_batch_axes():
    mesh = jax.make_mesh((1,), ("data",))
    rules = ShardingRules(mesh, shard_batch=False)
    assert rules.logical["batch"] is None


# ------------------------------------------------------------- local SGD
def test_fedavg_outer_is_pmean():
    mesh = jax.make_mesh((1,), ("pod",))

    def f(p):
        return local_sgd.fedavg_outer(p, "pod")

    p = {"w": jnp.arange(4.0)}
    out = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(),
                                    out_specs=P()))(p)
    np.testing.assert_allclose(out["w"], p["w"])          # 1 pod: identity


def test_outer_step_plain_fedavg_semantics():
    """outer_lr=1, momentum=0 ⇒ anchor ← mean(local params)."""
    mesh = jax.make_mesh((1,), ("pod",))
    cfg = local_sgd.LocalSGDConfig(outer_lr=1.0, outer_momentum=0.0,
                                   nesterov=False)
    anchor = {"w": jnp.zeros(3)}
    local = {"w": jnp.ones(3) * 2.0}

    def f(local_p):
        st = local_sgd.init_outer_state(anchor)
        new_anchor, _ = local_sgd.outer_step(local_p, st, cfg, "pod")
        return new_anchor

    out = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(),
                                    out_specs=P()))(local)
    np.testing.assert_allclose(out["w"], 2.0)             # = mean of locals


def test_outer_momentum_accumulates():
    mesh = jax.make_mesh((1,), ("pod",))
    cfg = local_sgd.LocalSGDConfig(outer_lr=0.5, outer_momentum=0.9,
                                   nesterov=True)
    anchor = {"w": jnp.zeros(2)}

    def f(local_p):
        st = local_sgd.init_outer_state(anchor)
        a1, st = local_sgd.outer_step(local_p, st, cfg, "pod")
        a2, st = local_sgd.outer_step(local_p, st, cfg, "pod")
        return a1, a2

    local = {"w": jnp.ones(2)}
    a1, a2 = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(),
                                       out_specs=P()))(local)
    assert abs(float(a2["w"][0])) > abs(float(a1["w"][0]))


def test_make_sharded_outer_single_pod_matches_outer_step():
    """1-pod mesh: the sharded sync == a direct outer_step on that pod."""
    mesh = jax.make_mesh((1,), ("pod",))
    cfg = local_sgd.LocalSGDConfig(outer_lr=1.0, outer_momentum=0.0,
                                   nesterov=False)
    anchor = {"w": jnp.zeros(3)}
    state = local_sgd.init_outer_state(anchor)
    local = {"w": jnp.ones((1, 3)) * 2.0}       # (n_pods=1, ...) stacked
    sync = local_sgd.make_sharded_outer(mesh, cfg)
    new_anchor, _ = sync(local, state)
    np.testing.assert_allclose(new_anchor["w"], 2.0)


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs multi-device CPU (run via ./test.sh)")
def test_make_sharded_outer_averages_divergent_pods():
    """2-pod mesh: pods that drifted apart sync to the cross-pod mean."""
    mesh = jax.make_mesh((2,), ("pod",))
    cfg = local_sgd.LocalSGDConfig(outer_lr=1.0, outer_momentum=0.0,
                                   nesterov=False)
    anchor = {"w": jnp.zeros(4)}
    state = local_sgd.init_outer_state(anchor)
    local = {"w": jnp.stack([jnp.full(4, 1.0), jnp.full(4, 3.0)])}
    sync = local_sgd.make_sharded_outer(mesh, cfg)
    new_anchor, _ = sync(local, state)
    np.testing.assert_allclose(new_anchor["w"], 2.0)      # mean of 1 and 3


# ------------------------------------------------------------- cost model
def test_jaxpr_cost_counts_scan_trips():
    W = jnp.ones((32, 32))
    x = jnp.ones((4, 32))

    def f(W):
        def body(c, _):
            return c @ W, None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return jnp.sum(y)

    flops = costmodel.jaxpr_cost(jax.make_jaxpr(f)(W))["flops"]
    want = 7 * 2 * 4 * 32 * 32
    assert abs(flops - want) / want < 0.05


def test_jaxpr_cost_grad_triples_dot_flops():
    W = jnp.ones((64, 64))
    x = jnp.ones((8, 64))
    fwd = costmodel.jaxpr_cost(
        jax.make_jaxpr(lambda w: jnp.sum(x @ w))(W))["flops"]
    bwd = costmodel.jaxpr_cost(
        jax.make_jaxpr(jax.grad(lambda w: jnp.sum(x @ w)))(W))["flops"]
    assert 1.5 < bwd / fwd < 2.6                  # fwd+wgrad (dgrad DCE'd)


def test_hlo_collective_parser_trip_counts():
    hlo = """
HloModule test

%cond (p: (s32[])) -> pred[] {
  %p = (s32[]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(12)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

%body (p: (s32[])) -> (s32[]) {
  %p = (s32[]) parameter(0)
  %ag = bf16[128,64] all-gather(%x), dimensions={0}
  ROOT %t = (s32[]) tuple(%i)
}

ENTRY %main (a: bf16[8]) -> bf16[8] {
  %w = (s32[]) while(%init), condition=%cond, body=%body
  %ar = f32[256] all-reduce(%y), to_apply=%add
  ROOT %r = bf16[8] copy(%a)
}
"""
    out = costmodel.hlo_collective_bytes(hlo)
    assert out["all-gather"] == 12 * 128 * 64 * 2         # ×12 trips
    assert out["all-reduce"] == 256 * 4


# ------------------------------------------------------------- SARIMA
@pytest.mark.slow
def test_sarima_fits_seasonal_series():
    t = np.arange(96 * 40, dtype=np.float64)
    series = (10 + 5 * np.sin(2 * np.pi * t / 96)
              + np.random.default_rng(0).normal(0, 0.3, len(t)))
    model = sarima.auto_fit(series[:96 * 30])
    fc = sarima.forecast(model, series[:96 * 30], 8)
    actual = series[96 * 30:96 * 30 + 8]
    mape = np.abs((fc - actual) / actual).mean()
    assert mape < 0.15, mape


def test_sarima_rolling_protocol_shapes():
    s = synthetic.generate_buildings("CA", [2], days=33)[0]
    pred, actual = sarima.rolling_forecast(s, lookahead=4, fit_days=30,
                                           horizon_days=1)
    assert pred.shape == actual.shape
    assert pred.shape[1] == 4
    assert np.isfinite(pred).all()


def test_hlo_parser_tuple_allreduce_and_pod_split():
    """Variadic tuple all-reduces sum all elements; pod classification
    catches both replica_groups and source_target_pairs."""
    hlo = """
HloModule t

ENTRY %main (a: bf16[8]) -> bf16[8] {
  %ar = (f32[10,10], f32[4,4]) all-reduce(%x, %y), replica_groups=[256,2]<=[2,256]T(1,0), to_apply=%add
  %cp = bf16[64] collective-permute(%z), source_target_pairs={{0,256},{256,0}}
  %ag = bf16[32,16] all-gather(%w), replica_groups=[32,16]<=[512], dimensions={0}
  ROOT %r = bf16[8] copy(%a)
}
"""
    out = costmodel.hlo_collective_bytes(hlo, pod_size=256)
    assert out["all-reduce"] == (100 + 16) * 4            # tuple summed
    assert out["collective-permute"] == 64 * 2
    # pod-spanning: the [256,2]<=[2,256]T(1,0) groups pair (i, i+256);
    # the permute pairs cross pods; the [32,16]<=[512] groups are intra-pod
    assert out["inter_pod"] == (100 + 16) * 4 + 64 * 2


def test_spans_pod_iota_formats():
    assert costmodel._spans_pod(
        "x replica_groups=[256,2]<=[2,256]T(1,0)", 256)
    assert not costmodel._spans_pod(
        "x replica_groups=[32,16]<=[512]", 256)
    assert costmodel._spans_pod(
        "x replica_groups={{0,300}}", 256)
    assert not costmodel._spans_pod(
        "x source_target_pairs={{0,1},{1,0}}", 256)
