from repro.kernels.paged_attention.ops import (paged_attention,
                                               paged_prefill_attention)
from repro.kernels.paged_attention.quant import (CACHE_DTYPES, dequantize,
                                                 is_quantized, pool_dtype,
                                                 quantize)
from repro.kernels.paged_attention.ref import (
    paged_attention_reference, paged_prefill_attention_reference, v_rows)

__all__ = ["paged_attention", "paged_attention_reference",
           "paged_prefill_attention", "paged_prefill_attention_reference",
           "CACHE_DTYPES", "dequantize", "is_quantized", "pool_dtype",
           "quantize", "v_rows"]
