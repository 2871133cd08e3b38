"""Pallas TPU kernels for the pruning framework's compute hot spots.

  nm_spmm        2:4-compressed weight × activation matmul (serving)
  hessian_accum  streaming H = 2·x·xᵀ over calibration tokens (pruning)
  nm_select      Eq. (12) per-group combination scoring → 𝔐 mask (pruning)
  flash_attn     online-softmax causal attention (32k prefill)
  paged_attn     block-table paged GQA decode attention (serve runtime)
  selective_scan Mamba-1 scan, state in VMEM (capture/propagate, prefill)

Each kernel has a pure-jnp oracle in ref.py and a jit'd public wrapper in
ops.py.  On this CPU container they are validated with interpret=True;
BlockSpecs are sized for TPU v5e VMEM (128-aligned MXU tiles).
"""

from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
