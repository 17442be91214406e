"""Pallas TPU kernels for the paper's compute hot-spots.

Each subpackage follows the contract: ``kernel.py`` (pl.pallas_call with
explicit BlockSpec VMEM tiling), ``ops.py`` (jit'd shape-flexible wrapper
with a use_pallas switch), ``ref.py`` (pure-jnp oracle). All validated in
interpret mode against the oracle over shape/dtype sweeps
(tests/test_kernels.py).

  int8_matmul      — w8a8 quantized matmul (npu_quant_matmul analogue, §4.7)
  gmm              — grouped expert FFN, gate/up/SiLU/down fused (§3.2/§5.2)
  decode_attention — flash-decoding GQA over the KV cache (Fig. 20 hot loop)
  quant_dispatch   — fused token-wise INT8 quantization for dispatch (§3.2)
  collect          — EPLB expert-load histogram (§4.5 step 1)
  route_pack       — dispatch packing: capacity rank on the scalar core and
                     one row DMA per kept assignment into its capacity
                     bucket, INT8 quantize ahead of it (§3.2/§4.7)

Wrapper ``interpret`` arguments default to ``None`` = auto: interpret
only when the active JAX backend is CPU (``kernels/runtime.py``), so the
same call sites compile for real on TPU.
"""
