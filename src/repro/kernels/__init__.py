from repro.kernels.flash_attn import flash_attention, flash_mha
from repro.kernels.fused_mac import (assert_draw_invariance,
                                     canonical_block_u, fused_channels,
                                     fused_mac, fused_mac_partials,
                                     fused_mac_ref, fused_noise,
                                     fused_partials_reduce)
from repro.kernels.ops import fused_combine, interpret_mode, mf_combine
from repro.kernels.ota_combine import ota_combine, ota_combine_batched
from repro.kernels.ref import (flash_attention_ref, ota_combine_ref,
                               ota_combine_ref_batched)

__all__ = ["mf_combine", "fused_combine", "ota_combine",
           "ota_combine_batched", "ota_combine_ref",
           "ota_combine_ref_batched", "fused_mac", "fused_mac_partials",
           "fused_mac_ref", "fused_noise", "fused_partials_reduce",
           "fused_channels", "assert_draw_invariance", "canonical_block_u",
           "flash_attention", "flash_mha", "flash_attention_ref",
           "interpret_mode"]
