"""Pallas TPU kernel for the Mamba-2 SSD intra-chunk block.

The chunked SSD algorithm (models/mamba2.py) splits into:
  (a) intra-chunk quadratic block  — compute-bound, MXU-friendly,
  (b) inter-chunk linear recurrence — tiny, carried by lax.scan in ops.py.

This kernel implements (a): for each (batch, head, chunk) it computes

  y_diag = (C B^T  ⊙  L) X        (Q,Q) x (Q,hd)
  state  = (B ⊙ decay_to_end)^T X  -> (N, hd) end-of-chunk contribution

where L = exp(segsum(a)) is the lower-triangular decay matrix.  The
cumulative log decay ``cum`` is a prefix sum over the chunk, which the
TPU's Pallas lowering has no primitive for, so the wrapper computes it
in XLA and streams it in twice: as a row (1, Q) and as a column (Q, 1).
The kernel then builds L from two broadcasts, with no in-kernel
transpose.

Grid: ``(B, nh, nchunks)``, all parallel.  Blocks: X (Q, hd), B/C (Q, N)
live wholly in VMEM — Q=chunk (<=256), hd<=64, N<=128 keeps the working
set ~(256x256 + 2x256x128 + 256x64) f32 ~ 0.4 MB, well under VMEM.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import resolve_interpret


def _ssd_chunk_kernel(
    x_ref,  # (1, 1, Q, hd)   x * dt
    row_ref,  # (1, 1, 1, Q)  cumulative log decay, as a row
    col_ref,  # (1, 1, Q, 1)  the same, as a column
    tail_ref,  # (1, 1, Q, 1) cum[-1] - cum: log decay to the chunk's end
    b_ref,  # (1, 1, Q, N)
    c_ref,  # (1, 1, Q, N)
    y_ref,  # (1, 1, Q, hd)   out: intra-chunk y
    s_ref,  # (1, 1, N, hd)   out: end-of-chunk state contribution
    *,
    chunk: int,
):
    x = x_ref[0, 0].astype(jnp.float32)  # (Q, hd)
    cum_row = row_ref[0, 0]  # (1, Q)
    cum_col = col_ref[0, 0]  # (Q, 1)
    B = b_ref[0, 0].astype(jnp.float32)  # (Q, N)
    C = c_ref[0, 0].astype(jnp.float32)  # (Q, N)

    # L[i, j] = exp(cum[i] - cum[j]) for i >= j else 0
    diff = cum_col - cum_row  # (Q, Q)
    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(i >= j, jnp.exp(diff), 0.0)  # (Q, Q)

    scores = jax.lax.dot_general(
        C, B, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q, Q) = C B^T
    y = jax.lax.dot_general(
        scores * L, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q, hd)

    decay_to_end = jnp.exp(tail_ref[0, 0])  # (Q, 1)
    state = jax.lax.dot_general(
        B * decay_to_end,
        x,
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (N, hd)

    y_ref[0, 0, :, :] = y.astype(y_ref.dtype)
    s_ref[0, 0, :, :] = state.astype(s_ref.dtype)


def ssd_intra_chunk(
    x: jax.Array,  # (B, nh, nC, Q, hd)  x * dt
    a: jax.Array,  # (B, nh, nC, Q)      log decays
    Bm: jax.Array,  # (B, nh, nC, Q, N)
    Cm: jax.Array,  # (B, nh, nC, Q, N)
    *,
    interpret: Optional[bool] = None,
):
    """Returns (y_diag (B,nh,nC,Q,hd), states (B,nh,nC,N,hd), cum (B,nh,nC,Q))."""
    B_, nh, nC, Q, hd = x.shape
    N = Bm.shape[-1]
    BH = B_ * nh
    cum = jnp.cumsum(a.astype(jnp.float32), axis=-1)  # (B, nh, nC, Q)
    cum_bh = cum.reshape(BH, nC, Q)
    xr = x.reshape(BH, nC, Q, hd)
    row = cum_bh[:, :, None, :]  # (BH, nC, 1, Q)
    col = cum_bh[:, :, :, None]  # (BH, nC, Q, 1)
    tail = cum_bh[:, :, -1:, None] - col  # (BH, nC, Q, 1)
    br = Bm.reshape(BH, nC, Q, N)
    cr = Cm.reshape(BH, nC, Q, N)

    kernel = functools.partial(_ssd_chunk_kernel, chunk=Q)
    y, s = pl.pallas_call(
        kernel,
        grid=(BH, nC),
        in_specs=[
            pl.BlockSpec((1, 1, Q, hd), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, c: (b, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, hd), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, N, hd), lambda b, c: (b, c, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, nC, Q, hd), jnp.float32),
            jax.ShapeDtypeStruct((BH, nC, N, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=resolve_interpret(interpret),
    )(xr, row, col, tail, br, cr)
    return (
        y.reshape(B_, nh, nC, Q, hd),
        s.reshape(B_, nh, nC, N, hd),
        cum,
    )
