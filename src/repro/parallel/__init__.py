from repro.parallel.sharding import (batch_shardings, cache_shardings,
                                     mesh_axes, paged_cache_shardings,
                                     param_spec, params_shardings,
                                     replicated, train_state_shardings)
from repro.parallel.work import merge_disjoint, round_robin_shard

__all__ = ["batch_shardings", "cache_shardings", "mesh_axes",
           "merge_disjoint", "paged_cache_shardings", "param_spec",
           "params_shardings", "replicated", "round_robin_shard",
           "train_state_shardings"]
