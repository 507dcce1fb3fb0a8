"""Pallas TPU kernels for the serving hot loops.

- ``decode.paged_decode_attention`` — decode-step attention that reads KV
  pages directly from HBM (fuses away the XLA path's [B, T, Hkv, Dh]
  gather; page-major slabs, one DMA per page), per-layer cache buffers.
- ``decode.paged_decode_attention_stacked`` — same kernel over the STACKED
  cache with an SMEM layer index: usable inside a ``lax.scan`` over layers,
  so the TPU decode step compiles one layer body instead of L.

The XLA implementations in ``dynamo_tpu.ops.attention`` remain the portable
reference (CPU tests).
"""

from dynamo_tpu.ops.pallas.decode import (
    paged_decode_attention,
    paged_decode_attention_stacked,
)
from dynamo_tpu.ops.pallas.mla_decode import (
    mla_paged_decode_layer,
    mla_paged_decode_stacked,
)
from dynamo_tpu.ops.pallas.mla_prefill import mla_paged_prefill_stacked
from dynamo_tpu.ops.pallas.ragged import ragged_mixed_attention_packed

__all__ = ["paged_decode_attention", "paged_decode_attention_stacked",
           "mla_paged_decode_layer", "mla_paged_decode_stacked",
           "mla_paged_prefill_stacked", "ragged_mixed_attention_packed"]
