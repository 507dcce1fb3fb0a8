"""Pallas TPU kernels for the serving hot loops.

Every kernel takes the STACKED page pool ``[L, N, 2, Hkv, ps, Dh]`` and a
layer index in SMEM (a python int or the traced index of a ``lax.scan``
over layers), so a step program compiles one layer body:

- ``decode.paged_decode_attention_stacked`` — decode-step (S == 1)
  attention that reads KV pages directly from HBM (fuses away the XLA
  path's [B, T, Hkv, Dh] gather; page-major slabs, one DMA per page).
- ``prefill.paged_prefill_attention_stacked`` — a padded ``[B, S]`` chunk
  batch; ``ragged.ragged_mixed_attention_packed`` — a token-packed step:
  the ragged kernel over its rows of several tokens, the decode kernel
  over its one-token rows.
- ``mla_decode.mla_paged_decode_stacked`` /
  ``mla_prefill.mla_paged_prefill_stacked`` /
  ``mla_ragged.mla_ragged_attention_packed`` — the same three step forms
  over DeepSeek's latent cache;
  ``mla_ragged.mla_masked_attention_packed`` /
  ``mla_decode_masked.mla_masked_decode_stacked`` — the rows of several
  tokens and of one of a model that attends a learned selection or a
  window ring: a bias over the streamed context.

The XLA implementations in ``dynamo_tpu.ops.attention`` remain the portable
reference (CPU tests).
"""

from dynamo_tpu.ops.pallas.decode import paged_decode_attention_stacked
from dynamo_tpu.ops.pallas.mla_decode import mla_paged_decode_stacked
from dynamo_tpu.ops.pallas.mla_prefill import mla_paged_prefill_stacked
from dynamo_tpu.ops.pallas.mla_ragged import mla_ragged_attention_packed
from dynamo_tpu.ops.pallas.ragged import ragged_mixed_attention_packed

__all__ = ["paged_decode_attention_stacked", "mla_paged_decode_stacked",
           "mla_paged_prefill_stacked", "mla_ragged_attention_packed",
           "ragged_mixed_attention_packed"]
