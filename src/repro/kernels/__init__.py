"""Pallas TPU kernels for the framework's hot spots (validated in interpret
mode on CPU).  The population engine's ``bd_impl="fused"`` path runs the
first three; ``bd_impl="einsum"`` runs none of them (DESIGN.md §7, §9):

  fused_input     — dense input projection + bias + per-segment activation
                    + padding mask in ONE pass, fused one-pass backward
  fused_layer     — block-diag projection + bias + per-segment activation in
                    ONE pass (act'(z) emitted in-register for the fused
                    backward; pre-activations never reach HBM — DESIGN.md §7)
  loss_head       — M3 output projection + per-member softmax
                    cross-entropy + dlogits in one pass per direction
  infer_head      — forward-only M3 head + bias (+ log-softmax) for serving
  moe_gemm        — grouped GEMM (M3's row-segment dual; MoE expert compute)
  flash_attention — fused online-softmax attention (causal/SWA/GQA), the
                    §Perf-identified lever for memory-bound attention cells

Their wrappers are in ``kernels/ops.py``, their shared tiling helpers and
VMEM budgets in ``kernels/tiling.py``.
"""
from repro.kernels.ops import flash_attention, fused_layer, moe_gemm

__all__ = ["flash_attention", "fused_layer", "moe_gemm"]
