"""Which way a Pallas kernel runs: compiled for the TPU, or interpreted.

A kernel called with ``interpret=None`` compiles natively when JAX's
default backend is a TPU and runs in the Pallas interpreter anywhere
else (the CPU tests).  An explicit ``True``/``False`` wins, which is how
the described-chip compile tests ask for the TPU lowering on a CPU host.
"""

from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
