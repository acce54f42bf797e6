"""Auto-parallelization search (the Unity capability, TPU-native).

The reference's Unity stack (reference src/runtime/graph.cc, substitution.cc,
simulator.cc, machine_model.cc — SURVEY §2.1 L6) jointly searches algebraic
graph substitutions and per-op MachineViews, costing candidates with an
on-device microbenchmark simulator. Here the same capability is rebuilt
TPU-first:

* the decision space per op is a **sharding assignment** (which named mesh
  axes shard which dims of its output/weights) instead of a MachineView —
  GSPMD inserts the collectives, so the searched object IS the PartitionSpec;
* the cost model is an analytic TPU roofline (MXU flops / HBM bytes / ICI
  collective bytes) with an optional on-device profiled refinement, instead
  of CUDA microbenchmarks;
* the DP search splits the PCG at post-dominator bottlenecks exactly like
  ``SearchHelper::find_optimal_sequence_graph_time`` and memoizes subgraph
  costs; an MCMC pass (MLSys'19 ``FFModel::mcmc_optimize``) refines;
* substitutions (``GraphXfer``) rewrite the PCG before/inside the search and
  load from the same JSON rule format as ``substitutions/graph_subst_3_v2.json``.
"""

from flexflow_tpu.search.machine_model import (
    TPU_CHIPS, ChipSpec, MachineModel,
)
from flexflow_tpu.search.strategy import OpStrategy, Strategy
from flexflow_tpu.search.cost_model import CostModel, CostMetrics
from flexflow_tpu.search.pcg import PCG, PCGNode
from flexflow_tpu.search.graph_search import (
    UnitySearch, data_parallel_model_strategy, mcmc_optimize, optimize_model,
)
from flexflow_tpu.search.measure import (
    format_ab, searched_vs_dp_wallclock,
)

__all__ = [
    "TPU_CHIPS", "ChipSpec", "MachineModel", "OpStrategy", "Strategy",
    "CostModel", "CostMetrics", "PCG", "PCGNode", "UnitySearch",
    "mcmc_optimize", "optimize_model", "data_parallel_model_strategy",
    "searched_vs_dp_wallclock", "format_ab",
]
