"""Pallas TPU kernels for the cascade's compute hot spots.

Each kernel package ships kernel.py (pl.pallas_call + BlockSpec),
ops.py (jit'd wrapper with CPU fallback) and ref.py (pure-jnp oracle).
The CPU tests run every kernel body in Pallas interpret mode;
tests/test_tpu_compile.py compiles the serve path's gate kernels for a
described TPU v5e, and ``chip_smoke.py`` checks them against their
oracles on the chip.
"""

from repro.kernels.confidence_gate.ops import confidence_gate
from repro.kernels.decode_attention.ops import decode_attn
from repro.kernels.flash_attention.ops import attention
from repro.kernels.fused_head_gate.ops import FusedLocalHead, fused_head_gate
from repro.kernels.maxconf.ops import maxconf
from repro.kernels.mdsa.ops import mdsa_distance
from repro.kernels.rwkv6_scan.ops import rwkv6_time_mix_scan

__all__ = ["confidence_gate", "fused_head_gate", "FusedLocalHead",
           "maxconf", "mdsa_distance", "attention", "decode_attn",
           "rwkv6_time_mix_scan"]
