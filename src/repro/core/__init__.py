"""SpChar core: the paper's contribution as a composable library.

Public API:
  CSR / BSR / ELLBSR / SELLBSR / DIA  sparse containers (csr.py)
  characterize / branch_entropy / reuse_affinity / index_affinity /
  thread_imbalance                static input metrics (metrics.py, Eq. 1-6)
  GENERATORS / TABLE2             synthetic matrices (synthetic.py, Table 2)
  corpus                          SuiteSparse-like corpus (dataset.py)
  DecisionTreeRegressor / kfold_cv  tree analysis engine (decision_tree.py)
  PLATFORMS                       TPU machine models (platforms.py)
  spmv_counters / ...             schedule counters = PMC analogue (counters.py)
  run_spmv_model / ...            roofline perf model (perfmodel.py)
  characterize_slice / compare_platforms   the characterization loop (charloop.py)
  ScheduleTuner                   loop-driven autotuning (autotune.py)
"""
from .csr import CSR, BSR, DIA, ELLBSR, SELLBSR
from .metrics import (branch_entropy, reuse_affinity, index_affinity,
                      thread_imbalance, partition_imbalance, characterize,
                      sell_slice_widths, sell_padding_fraction,
                      slice_imbalance, THREAD_SWEEP, FEATURE_NAMES)
from .synthetic import GENERATORS, TABLE2
from .dataset import corpus, DOMAINS
from .decision_tree import DecisionTreeRegressor, kfold_cv, mape, r2_score
from .platforms import Platform, PLATFORMS, TPU_V4, TPU_V5E, TPU_V5P, ROOFLINE_PLATFORM
from .counters import (spmv_counters, sell_spmv_counters, spgemm_counters,
                       spadd_counters, shard_counters)
from .perfmodel import (run_spmv_model, run_spmv_sell_model, run_spgemm_model,
                        run_spadd_model, execution_time, targets,
                        stall_breakdown)
from .charloop import (build_slice, characterize_slice, characterize_all,
                       compare_platforms, grouped_importance, CharacterizationResult)
from .autotune import ScheduleTuner, Schedule, select_moe_block_size

__all__ = [
    "CSR", "BSR", "DIA", "ELLBSR", "SELLBSR", "branch_entropy", "reuse_affinity", "index_affinity",
    "thread_imbalance", "partition_imbalance", "characterize", "THREAD_SWEEP",
    "FEATURE_NAMES", "GENERATORS", "TABLE2", "corpus", "DOMAINS",
    "DecisionTreeRegressor", "kfold_cv", "mape", "r2_score", "Platform",
    "PLATFORMS", "TPU_V4", "TPU_V5E", "TPU_V5P", "ROOFLINE_PLATFORM",
    "sell_slice_widths", "sell_padding_fraction", "slice_imbalance",
    "spmv_counters", "sell_spmv_counters", "spgemm_counters", "spadd_counters",
    "shard_counters",
    "run_spmv_model", "run_spmv_sell_model", "run_spgemm_model",
    "run_spadd_model", "execution_time", "targets",
    "stall_breakdown", "build_slice", "characterize_slice", "characterize_all",
    "compare_platforms", "grouped_importance", "CharacterizationResult",
    "ScheduleTuner", "Schedule", "select_moe_block_size",
]
