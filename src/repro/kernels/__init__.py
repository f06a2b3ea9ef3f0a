from repro.kernels.autotune import (REGISTRY, AutotuneRegistry,
                                    measure_enabled, measure_runtime)
from repro.kernels.gee_spmm import (choose_block_sizes, gee_spmm,
                                    measured_block_search)
from repro.kernels.gee_fused import gee_fused_from_bucketed, gee_spmm_fused
from repro.kernels.row_norm import row_norm
from repro.kernels.topk_score import (fused_topk_enabled, gathered_scores,
                                      masked_topk, pairwise_scores,
                                      scored_topk, scored_topk_gathered)

__all__ = ["gee_spmm", "choose_block_sizes", "measured_block_search",
           "row_norm", "gee_spmm_fused", "gee_fused_from_bucketed",
           "pairwise_scores", "gathered_scores", "masked_topk",
           "scored_topk", "scored_topk_gathered", "fused_topk_enabled",
           "REGISTRY", "AutotuneRegistry", "measure_enabled",
           "measure_runtime"]
