"""Pallas TPU flash attention (causal / sliding-window / logit-softcap).

The TPU-native statement of the chunked attention in models/layers.py:
online-softmax accumulation over key blocks, with explicit BlockSpec VMEM
tiling sized for the MXU (block dims multiples of 128 on real hardware;
tests shrink them).

Grid: ``(batch, q_heads, nq, nk)`` — the first three dims are parallel,
the key-block dim is ``arbitrary`` (sequential) so the f32 accumulator,
running max and running sum live in VMEM scratch across key blocks.
GQA is expressed in the K/V index maps (``h // q_per_kv``), so K/V blocks
are fetched once per KV head regardless of the query-head fan-out.

Layout: (B, H, S, hd) — ops.py transposes from the model's (B, S, H, hd).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import resolve_interpret

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


def _flash_kernel(
    q_ref,  # (1, 1, Bq, hd)
    k_ref,  # (1, 1, Bk, hd)
    v_ref,  # (1, 1, Bk, hd)
    o_ref,  # (1, 1, Bq, hd)
    acc_ref,  # VMEM scratch (Bq, hd) f32
    m_ref,  # VMEM scratch (Bq, 128) f32  (TPU wants a lane dim)
    l_ref,  # VMEM scratch (Bq, 128) f32
    *,
    scale: float,
    causal: bool,
    window: Optional[int],
    softcap: Optional[float],
    block_q: int,
    block_k: int,
    num_k_blocks: int,
):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)  # (Bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)  # (Bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Bq, Bk)
    s = s * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    ok = jnp.ones((block_q, block_k), dtype=bool)
    if causal:
        ok &= q_pos >= k_pos
    if window is not None:
        ok &= (q_pos - k_pos) < window
    s = jnp.where(ok, s, DEFAULT_MASK_VALUE)

    m_prev = m_ref[:, 0]  # (Bq,)
    m_cur = jnp.max(s, axis=1)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = l_ref[:, 0] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(j == num_k_blocks - 1)
    def _finish():
        l = l_ref[:, 0]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 output
        o_ref[0, 0, :, :] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_bhsd(
    q: jax.Array,  # (B, H, Sq, hd)
    k: jax.Array,  # (B, K, Sk, hd)
    v: jax.Array,  # (B, K, Sk, hd)
    *,
    scale: float,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    B, H, Sq, hd = q.shape
    K, Sk = k.shape[1], k.shape[2]
    assert H % K == 0
    q_per_kv = H // K
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0
    nq, nk = Sq // block_q, Sk // block_k

    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        window=window,
        softcap=softcap,
        block_q=block_q,
        block_k=block_k,
        num_k_blocks=nk,
    )
    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec(
                (1, 1, block_k, hd),
                lambda b, h, i, j, q_per_kv=q_per_kv: (b, h // q_per_kv, j, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_k, hd),
                lambda b, h, i, j, q_per_kv=q_per_kv: (b, h // q_per_kv, j, 0),
            ),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=resolve_interpret(interpret),
    )(q, k, v)
