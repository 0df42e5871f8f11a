"""Pallas TPU kernels for the paper's compute hot-spots.

  quant_matmul     fused unpack+dequant+matmul over packed LQ weights
  act_quant        fused runtime per-region activation quantization
  lut_matmul       paper section-V look-up-table scheme (one-hot partial sums)
  expert_matmul    quant_matmul grouped over packed experts (drop-free MoE)
  paged_attention  fused flash-decode over wire-format KV pages
                   (in-register affine/LUT dequant + online softmax)

Each kernel has a pure-jnp oracle in ref.py (paged_attention's oracle is
the model-layer gather+dequant path); ops.py holds the public jit'd
wrappers with backend selection (pallas / interpret / ref).
"""
from . import ops, ref, paged_attention
from .ops import (QWeight, quantize_weight, dequantize_weight, quant_matmul,
                  act_quant, lut_matmul, quant_dense, expert_matmul)

__all__ = ["ops", "ref", "paged_attention", "QWeight", "quantize_weight",
           "dequantize_weight", "quant_matmul", "act_quant", "lut_matmul",
           "quant_dense", "expert_matmul"]
