"""Pallas TPU kernels — the ``csrc/`` equivalent (SURVEY.md §2.3).

Every CUDA extension in the reference maps to a Pallas kernel here (TPU's
native kernel path); kernels fall back to the Pallas interpreter off-TPU so
the CPU test backbone exercises identical semantics.
"""

from apex_tpu.kernels.blockwise_attention import blockwise_attention
from apex_tpu.kernels.layer_norm import layer_norm, rms_norm
from apex_tpu.kernels.softmax import (
    generic_scaled_masked_softmax,
    scaled_masked_softmax,
    scaled_upper_triang_masked_softmax,
)
from apex_tpu.kernels.xentropy import softmax_cross_entropy
from apex_tpu.kernels.decode_attention import (
    cache_write_columns,
    cache_write_columns_quant,
    cache_write_columns_xla,
    decode_attention,
    decode_attention_quantized,
    kv_storage_dtype,
    live_rows,
    paged_attention,
    paged_attention_quantized,
    paged_gather_xla,
    paged_write_column,
    paged_write_column_quant,
    paged_write_columns,
    paged_write_columns_quant,
    paged_write_columns_xla,
    quantize_kv_rows,
    stacked_decode_attention,
    stacked_write_columns,
)
from apex_tpu.kernels.flash_attention import (
    flash_attention,
    flash_attention_bsh,
    flash_attention_with_lse,
    flash_bsh_eligible,
    mha,
)
from apex_tpu.kernels.flat_ops import (
    adagrad_flat,
    adam_flat,
    axpby_flat,
    l2norm_flat,
    scale_flat,
    sgd_flat,
)

__all__ = [
    "blockwise_attention",
    "layer_norm",
    "rms_norm",
    "generic_scaled_masked_softmax",
    "scaled_masked_softmax",
    "scaled_upper_triang_masked_softmax",
    "softmax_cross_entropy",
    "cache_write_columns",
    "cache_write_columns_quant",
    "cache_write_columns_xla",
    "decode_attention",
    "decode_attention_quantized",
    "kv_storage_dtype",
    "live_rows",
    "paged_attention",
    "paged_attention_quantized",
    "paged_gather_xla",
    "paged_write_column",
    "paged_write_column_quant",
    "paged_write_columns",
    "paged_write_columns_quant",
    "paged_write_columns_xla",
    "quantize_kv_rows",
    "stacked_decode_attention",
    "stacked_write_columns",
    "flash_attention",
    "flash_attention_bsh",
    "flash_attention_with_lse",
    "flash_bsh_eligible",
    "mha",
    "adagrad_flat",
    "adam_flat",
    "axpby_flat",
    "l2norm_flat",
    "scale_flat",
    "sgd_flat",
]
