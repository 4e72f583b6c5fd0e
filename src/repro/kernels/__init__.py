"""Pallas TPU kernels (DESIGN §3), compiled on a TPU and interpreted on
the CPU (``ops.interpret_mode``):
  sl_matmul — fused (BA ⊕ S)x with tile-local VMEM densify,
  sddmm     — sparse-support gradient dV = (xᵀdy)_I without the HBM transient,
  adam8bit  — fused blockwise 8-bit Adam update,
  sparse_decode — factored decode matmul x·S (tile-CSR, S never in HBM),
  paged_attention — decode and chunked-prefill attention over KV pages.
ops.py holds the jit wrappers + custom-VJP linear; ref.py the jnp oracles."""
from repro.kernels import ops, ref  # noqa: F401
