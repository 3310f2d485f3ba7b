# Pallas TPU kernels for the compute hot spots (validated on CPU via
# interpret=True against each ref.py oracle):
#   flash_attention — causal GQA attention (all attention archs)
#   moe_gemm        — grouped/block-diagonal GEMM (TD-Orch Phase 3 for MoE)
#   histogram       — contention-detection bincount (TD-Orch Phase 1)
#   segment_combine — merge-able ⊗-combine (TD-Orch Phase 4 / DistEdgeMap)
#   mamba_scan      — Mamba2 SSD chunk scan (zamba2 backbone)
#   flash_decode    — single-token decode attention over long KV caches
from .flash_attention.ops import attention
from .flash_decode.ops import decode_attention
from .histogram.ops import count_ids
from .mamba_scan.ops import mamba_ssd
from .moe_gemm.ops import grouped_gemm
from .segment_combine.ops import combine_add

__all__ = ["attention", "decode_attention", "count_ids", "mamba_ssd",
           "grouped_gemm", "combine_add"]
