from repro.kernels.lowering import resolve_mode, supports_pallas_lowering
from repro.kernels.ops import (amm_gather, flash_prefill, kv_decode,
                               pack_amm_banks, ssd_chunk, weight_stream)

__all__ = ["amm_gather", "flash_prefill", "kv_decode", "ssd_chunk",
           "pack_amm_banks", "weight_stream", "resolve_mode",
           "supports_pallas_lowering"]
