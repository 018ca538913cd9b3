"""The paper's contribution: FedAvg with clustering + EW-MSE, generalized
into a composable federated pipeline (select -> local-update ->
transform(deltas) -> aggregate -> server-update) with pluggable samplers,
delta transforms (clip / DP noise / quantize), aggregation topologies
(flat / hierarchical edge->region->cloud) and server optimizers, plus its
extension to cross-pod local-SGD training."""
from repro.core import (aggregation, clustering, fedavg, local_sgd, losses,
                        sampling, sarima, server_opt, transforms)
from repro.core.aggregation import (Aggregator, FlatAggregator,
                                    HierarchicalAggregator, LocalAggregator,
                                    make_aggregator)
from repro.core.fedavg import (FLResult, RoundEngine, evaluate_global,
                               evaluate_unseen_clients, make_pipeline_round,
                               pipeline_round, run_federated_training)
from repro.core.transforms import (DeltaTransform, GaussianNoise, L2Clip,
                                   StochasticQuantize, TransformStack,
                                   make_stack)
from repro.core.local_sgd import (LocalSGDConfig, OuterState, fedavg_outer,
                                  init_outer_state, outer_step)
from repro.core.losses import (accuracy, ew_mse, make_loss, mape, mse,
                               per_horizon_accuracy, rmse, weighted_ce)
from repro.core.sampling import SAMPLING_STRATEGIES, make_sampler
from repro.core.server_opt import (SERVER_OPTS, ServerState,
                                   init_server_state, server_update)

__all__ = ["aggregation", "clustering", "fedavg", "local_sgd", "losses",
           "sampling", "sarima", "server_opt", "transforms",
           "Aggregator", "FlatAggregator", "HierarchicalAggregator",
           "LocalAggregator", "make_aggregator",
           "DeltaTransform", "GaussianNoise", "L2Clip", "StochasticQuantize",
           "TransformStack", "make_stack",
           "FLResult", "RoundEngine", "evaluate_global",
           "evaluate_unseen_clients", "make_pipeline_round", "pipeline_round",
           "run_federated_training", "LocalSGDConfig",
           "OuterState", "fedavg_outer", "init_outer_state", "outer_step",
           "accuracy", "ew_mse", "make_loss", "mape", "mse",
           "per_horizon_accuracy", "rmse", "weighted_ce",
           "SAMPLING_STRATEGIES", "make_sampler", "SERVER_OPTS",
           "ServerState", "init_server_state", "server_update"]
