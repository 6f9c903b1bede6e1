"""Pallas TPU flash-decode: single-token attention over a long KV cache.

Decode is memory-bound: the whole KV cache streams HBM->VMEM once per new
token.  The kernel tiles the cache sequence dim into VMEM blocks and
accumulates online-softmax partials in scratch; all ``q_per_kv`` query
heads of one KV head share each K/V block fetch (GQA-aware, so HBM
traffic is sized by KV heads, not query heads).

Sliding-window layers bound their reads: key blocks wholly outside
``[pos - window, pos)`` are masked here and *skipped* on real hardware via
the grid (``nk`` covers only the window when ``window`` is static).

Grid: ``(B, K, nk)`` with the key-block dim sequential.
Layout: q (B, H, hd); cache (B, K, S, hd); lengths (B,) valid entries.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import resolve_interpret

MASK = -0.7 * float(jnp.finfo(jnp.float32).max)


def _decode_kernel(
    len_ref,  # SMEM (B,) int32
    q_ref,  # (1, 1, q_per_kv, hd)
    k_ref,  # (1, 1, Bk, hd)
    v_ref,  # (1, 1, Bk, hd)
    o_ref,  # (1, 1, q_per_kv, hd)
    acc_ref,  # VMEM (q_per_kv, hd) f32
    m_ref,  # VMEM (q_per_kv, 128) f32
    l_ref,  # VMEM (q_per_kv, 128) f32
    *,
    scale: float,
    window: Optional[int],
    softcap: Optional[float],
    block_k: int,
    num_k_blocks: int,
):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)  # (q_per_kv, hd)
    k = k_ref[0, 0].astype(jnp.float32)  # (Bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (q_per_kv, Bk)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    length = len_ref[b]
    k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    ok = k_pos < length
    if window is not None:
        ok &= k_pos >= (length - window)
    s = jnp.where(ok, s, MASK)

    m_prev = m_ref[:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_ref[...] = jnp.broadcast_to(
        (l_ref[:, 0] * alpha + jnp.sum(p, axis=1))[:, None], l_ref.shape
    )
    m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(j == num_k_blocks - 1)
    def _finish():
        l = l_ref[:, 0]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention_bkh(
    q: jax.Array,  # (B, H, hd)
    k_cache: jax.Array,  # (B, K, S, hd)
    v_cache: jax.Array,  # (B, K, S, hd)
    lengths: jax.Array,  # (B,) int32 — number of valid cache entries
    *,
    scale: float,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    block_k: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    B, H, hd = q.shape
    K, S = k_cache.shape[1], k_cache.shape[2]
    assert H % K == 0
    q_per_kv = H // K
    block_k = min(block_k, S)
    assert S % block_k == 0
    nk = S // block_k

    qg = q.reshape(B, K, q_per_kv, hd)
    kernel = functools.partial(
        _decode_kernel,
        scale=scale,
        window=window,
        softcap=softcap,
        block_k=block_k,
        num_k_blocks=nk,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, K, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, q_per_kv, hd), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_per_kv, hd), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K, q_per_kv, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_per_kv, hd), jnp.float32),
            pltpu.VMEM((q_per_kv, 128), jnp.float32),
            pltpu.VMEM((q_per_kv, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=resolve_interpret(interpret),
    )(lengths, qg, k_cache, v_cache)
    return out.reshape(B, H, hd)
