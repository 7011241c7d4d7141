"""Pallas TPU kernels for the hot ops of the decode/prefill path.

The reference has no in-tree kernels at all — it shells out to llama.cpp
(SURVEY.md section 2.3, runtime/src/model_manager.rs:187-204). Here the hot
loops are owned by this package:

  * ``flash_attention`` — blockwise causal attention for prefill/training.
    Never materializes the [T, S] score matrix, which is what makes 8k+
    contexts fit in a single chip's HBM (a naive prefill at T=8192 would
    allocate ~8.6 GB of fp32 scores per layer).
  * ``decode_attention`` — ragged batched-decode attention over the slot KV
    cache. Manually DMAs only the valid rows [0, length] of each slot from
    HBM (double-buffered), so short sequences don't pay full-context
    bandwidth.
  * ``quantized_matmul`` — int8-weight x bf16-activation matmul with
    per-output-channel scales; weights stream from HBM as int8 (half the
    bytes of bf16), dequantized in VMEM right before hitting the MXU.

Every kernel has a pure-jnp reference implementation used (a) where the CPU
is the intended backend (JAX_PLATFORMS=cpu: tests and CPU smokes), and (b)
as the ground truth for numeric parity tests (kernels additionally run in
interpret mode on CPU in tests).
"""

from __future__ import annotations

import os

from .. import backend
from .decode_attention import (
    decode_attention,
    decode_attention_int8,
    decode_attention_int8_reference,
    decode_attention_reference,
)
from .flash_attention import flash_attention, flash_attention_reference
from .paged_attention import (
    gather_pages,
    paged_decode_attention,
    paged_decode_attention_int8,
    paged_decode_attention_int8_reference,
    paged_decode_attention_reference,
    merge_heads,
    split_heads,
    window_decode_attention,
    write_rows,
)
from .paged_mla_attention import (
    paged_mla_decode_attention,
    paged_mla_decode_attention_reference,
)
from .int4_matmul import (
    dequantize_int4,
    int4_matmul,
    int4_matmul_reference,
    quantize_int4,
)
from .quantized_matmul import (
    dequantize,
    quantize_int8,
    quantized_matmul,
    quantized_matmul_reference,
)
from .verify_attention import (
    multiquery_decode_attention,
    multiquery_decode_attention_int8,
    multiquery_decode_attention_int8_reference,
    multiquery_decode_attention_reference,
)

__all__ = [
    "flash_attention",
    "flash_attention_reference",
    "decode_attention",
    "decode_attention_int8",
    "decode_attention_int8_reference",
    "decode_attention_reference",
    "paged_decode_attention",
    "paged_decode_attention_int8",
    "paged_decode_attention_int8_reference",
    "paged_decode_attention_reference",
    "window_decode_attention",
    "paged_mla_decode_attention",
    "paged_mla_decode_attention_reference",
    "gather_pages",
    "merge_heads",
    "split_heads",
    "write_rows",
    "multiquery_decode_attention",
    "multiquery_decode_attention_int8",
    "multiquery_decode_attention_int8_reference",
    "multiquery_decode_attention_reference",
    "quantize_int8",
    "dequantize",
    "quantized_matmul",
    "quantized_matmul_reference",
    "quantize_int4",
    "dequantize_int4",
    "int4_matmul",
    "int4_matmul_reference",
    "use_pallas",
]


def use_pallas() -> bool:
    """True when the Pallas kernel path should be used.

    The kernels are Mosaic-only, so they serve exactly when the process
    serves on a TPU (``backend.on_tpu()`` — which raises when the CPU was
    not asked for and no TPU came up, instead of quietly handing every
    call site the jnp reference). ``AIOS_TPU_NO_PALLAS=1`` forces the
    reference path on the chip (debugging / A-B benchmarking).
    """
    if os.environ.get("AIOS_TPU_NO_PALLAS", "").lower() in ("1", "true"):
        return False
    return backend.on_tpu()
