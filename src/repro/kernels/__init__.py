"""Pallas TPU kernels for the framework's compute hot spots.

The paper (Matchmaker Paxos) is a control-plane contribution with no
kernel of its own; these kernels serve the *data plane* the control plane
manages: flash attention (causal / sliding-window / softcap), flash-decode
attention over long KV caches, and the Mamba-2 SSD intra-chunk block.

Validated in the Pallas interpreter on CPU against the ref.py jnp
oracles; compiled natively on a TPU backend (kernels/backend.py), and
compiled for a described v5e in tests/kernels/test_tpu_compile.py.
"""

from . import ops, ref
from .decode_attention import decode_attention_bkh
from .flash_attention import flash_attention_bhsd
from .ssd_scan import ssd_intra_chunk

__all__ = [
    "ops",
    "ref",
    "decode_attention_bkh",
    "flash_attention_bhsd",
    "ssd_intra_chunk",
]
