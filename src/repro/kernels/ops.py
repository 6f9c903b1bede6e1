"""Jit'd wrappers: the public kernel API used by the model layer.

Each op accepts ``use_pallas`` / ``interpret`` switches.  ``interpret``
defaults to the backend (kernels/backend.py): on a TPU the Pallas path
compiles natively, elsewhere it runs in the interpreter (tests).
``use_pallas=False`` takes the jnp reference instead.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from . import ref
from .decode_attention import decode_attention_bkh
from .flash_attention import flash_attention_bhsd
from .ssd_scan import ssd_intra_chunk


# --------------------------------------------------------------------------
# Flash attention in the model's (B, S, H, hd) layout
# --------------------------------------------------------------------------
@partial(
    jax.jit,
    static_argnames=(
        "causal",
        "window",
        "softcap",
        "scale",
        "block_q",
        "block_k",
        "use_pallas",
        "interpret",
    ),
)
def flash_attention(
    q: jax.Array,  # (B, Sq, H, hd)
    k: jax.Array,  # (B, Sk, K, hd)
    v: jax.Array,
    *,
    scale: float,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
) -> jax.Array:
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if use_pallas:
        out = flash_attention_bhsd(
            qt,
            kt,
            vt,
            scale=scale,
            causal=causal,
            window=window,
            softcap=softcap,
            block_q=block_q,
            block_k=block_k,
            interpret=interpret,
        )
    else:
        out = ref.flash_attention_ref(
            qt, kt, vt, scale=scale, causal=causal, window=window, softcap=softcap
        )
    return out.transpose(0, 2, 1, 3)


@partial(
    jax.jit,
    static_argnames=("scale", "window", "softcap", "block_k", "use_pallas", "interpret"),
)
def decode_attention(
    q: jax.Array,  # (B, 1, H, hd)
    k_cache: jax.Array,  # (B, S, K, hd)
    v_cache: jax.Array,
    lengths: jax.Array,  # (B,)
    *,
    scale: float,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    block_k: int = 256,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
) -> jax.Array:
    qt = q[:, 0]  # (B, H, hd)
    kt = k_cache.transpose(0, 2, 1, 3)  # (B, K, S, hd)
    vt = v_cache.transpose(0, 2, 1, 3)
    if use_pallas:
        out = decode_attention_bkh(
            qt,
            kt,
            vt,
            lengths.astype(jnp.int32),
            scale=scale,
            window=window,
            softcap=softcap,
            block_k=block_k,
            interpret=interpret,
        )
    else:
        out = ref.decode_attention_ref(
            qt, kt, vt, lengths, scale=scale, window=window, softcap=softcap
        )
    return out[:, None]


# --------------------------------------------------------------------------
# Full SSD (kernel intra-chunk + lax.scan inter-chunk glue)
# --------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("chunk", "use_pallas", "interpret"))
def ssd(
    x: jax.Array,  # (B, S, nh, hd)  pre-multiplied by dt
    a: jax.Array,  # (B, S, nh)      log decays (dt * A)
    Bm: jax.Array,  # (B, S, N)
    Cm: jax.Array,  # (B, S, N)
    *,
    chunk: int = 256,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
):
    """Mirror of models.mamba2.ssd_chunked with the intra-chunk block on
    the Pallas kernel.  Returns (y (B,S,nh,hd), final_state (B,nh,hd,N))."""
    B_, S, nh, hd = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    nC = S // Q
    assert nC * Q == S
    xc = x.reshape(B_, nC, Q, nh, hd).transpose(0, 3, 1, 2, 4)  # (B,nh,nC,Q,hd)
    ac = a.reshape(B_, nC, Q, nh).transpose(0, 3, 1, 2)  # (B,nh,nC,Q)
    Bc = jnp.broadcast_to(
        Bm.reshape(B_, 1, nC, Q, N), (B_, nh, nC, Q, N)
    )
    Cc = jnp.broadcast_to(
        Cm.reshape(B_, 1, nC, Q, N), (B_, nh, nC, Q, N)
    )

    if use_pallas:
        y_diag, states, cum = ssd_intra_chunk(xc, ac, Bc, Cc, interpret=interpret)
    else:
        y_diag, states, cum = ref.ssd_intra_chunk_ref(xc, ac, Bc, Cc)

    # inter-chunk recurrence (linear scan over chunks)
    chunk_decay = jnp.exp(cum[..., -1])  # (B, nh, nC)
    h0 = jnp.zeros((B_, nh, N, hd), jnp.float32)

    def step(h, inp):
        st, dec = inp  # (B,nh,N,hd), (B,nh)
        h_in = h
        return h * dec[..., None, None] + st, h_in

    sts = states.transpose(2, 0, 1, 3, 4)  # (nC, B, nh, N, hd)
    decs = chunk_decay.transpose(2, 0, 1)
    h_final, h_ins = jax.lax.scan(step, h0, (sts, decs))

    state_decay_out = jnp.exp(cum)  # (B, nh, nC, Q)
    y_off = jnp.einsum(
        "bhcqn,bhcnp,bhcq->bhcqp",
        Cc.astype(jnp.float32),
        h_ins.transpose(1, 2, 0, 3, 4),
        state_decay_out,
    )
    y = (y_diag + y_off).transpose(0, 2, 3, 1, 4).reshape(B_, S, nh, hd)
    # final state in models/mamba2.py layout (B, nh, hd, N)
    return y.astype(x.dtype), h_final.transpose(0, 1, 3, 2).astype(x.dtype)
