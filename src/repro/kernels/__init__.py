from repro.kernels import autotune, ref
from repro.kernels.backend import resolve_interpret
from repro.kernels.ops import (cohort_scatter, cohort_scatter_tree,
                               decode_attention_cache, exit_head_fused,
                               exit_update_fused, flash_attention_bshd,
                               paged_gather, rmsnorm_fused,
                               softmax_confidence_fused)

__all__ = ["autotune", "ref", "resolve_interpret",
           "softmax_confidence_fused", "rmsnorm_fused",
           "flash_attention_bshd", "decode_attention_cache", "paged_gather",
           "exit_update_fused", "exit_head_fused", "cohort_scatter",
           "cohort_scatter_tree"]
