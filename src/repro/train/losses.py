"""Sequence-chunked softmax cross-entropy.

The (B, S, V) logits tensor is the memory cliff for 256k-vocab configs:
at grok-1's train_4k shape the full fp32 logits would be ~0.5 TB.  We
scan over ``n_chunks`` sequence chunks, materializing only (B, S/c, V) at
a time; the backward pass re-forms each chunk under the same scan (remat
by construction).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import softcap

Array = jax.Array


@jax.named_scope("head")
def chunked_xent(
    cfg: ModelConfig,
    params: Any,
    hidden: Array,  # (B, S, D)
    targets: Array,  # (B, S)
    mask: Optional[Array] = None,  # (B, S)
    n_chunks: Optional[int] = None,
) -> Tuple[Array, Dict[str, Array]]:
    B, S, D = hidden.shape
    n_chunks = n_chunks or cfg.loss_seq_chunks
    while S % n_chunks != 0:
        n_chunks -= 1
    C = S // n_chunks
    w = params.get("unembed")
    if w is None:
        w = params["embed"].T  # (D, V)
    w = w.astype(hidden.dtype)
    if mask is None:
        mask = jnp.ones((B, S), jnp.float32)

    hc = hidden.reshape(B, n_chunks, C, D).transpose(1, 0, 2, 3)
    tc = targets.reshape(B, n_chunks, C).transpose(1, 0, 2)
    mc = mask.reshape(B, n_chunks, C).transpose(1, 0, 2)

    def body(carry, inp):
        loss_sum, correct = carry
        h, t, m = inp
        logits = jnp.einsum("bcd,dv->bcv", h, w).astype(jnp.float32)
        logits = softcap(logits, cfg.final_logit_softcap)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, t[..., None], axis=-1)[..., 0]
        loss_sum = loss_sum + jnp.sum((lse - ll) * m)
        correct = correct + jnp.sum((jnp.argmax(logits, -1) == t) * m)
        return (loss_sum, correct), None

    (loss_sum, correct), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)), (hc, tc, mc)
    )
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    return loss_sum / denom, {"accuracy": correct / denom, "tokens": denom}
