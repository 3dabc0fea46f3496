"""Pallas-TPU kernels for the performance-critical compute layers.

Each kernel package has kernel.py (pl.pallas_call + BlockSpec VMEM
tiling), ops.py (jit wrapper) and ref.py (pure-jnp oracle).  Kernels are
validated against their oracles in interpret mode on CPU
(tests/test_kernels.py), compiled for a described TPU v5e
(tests/test_tpu_compile.py), and run on the chip by chip_smoke.py.  The
model path does not call them yet.
"""

from .decode_attention import decode_attention
from .decode_attention import decode_attention_ref
from .flash_attention import attention_ref
from .flash_attention import flash_attention
from .ssd_scan import ssd_ref
from .ssd_scan import ssd_scan
from .ssd_scan import ssd_sequential_ref

__all__ = ["decode_attention", "decode_attention_ref", "attention_ref",
           "flash_attention", "ssd_ref", "ssd_scan", "ssd_sequential_ref"]
