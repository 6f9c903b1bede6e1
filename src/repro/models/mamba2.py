"""Mamba-2 (SSD — state-space duality) block, pure-functional JAX.

Training/prefill uses the chunked SSD algorithm of the Mamba-2 paper
(arXiv:2405.21060, "ssd_minimal"): intra-chunk quadratic attention-like
blocks plus an inter-chunk recurrence on the (heads, head_dim, state)
tensor.  We carry the inter-chunk recurrence with ``lax.scan`` (linear in
chunk count, constant memory) instead of the paper's quadratic
``decay_chunk`` matrix so the 500k-token shapes stay feasible.

Decode is the O(1)-per-token recurrent form over a persistent
(B, heads, head_dim, state) SSM state plus a rolling conv window —
constant-size state is exactly why the assignment routes ``long_500k`` to
the SSM/hybrid architectures.

The intra-chunk einsum block is the compute hot spot; kernels/ssd_scan.py
provides the Pallas TPU version, and this file doubles as its oracle.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import rms_norm

Array = jax.Array


# --------------------------------------------------------------------------
# Init
# --------------------------------------------------------------------------
def mamba_init(cfg: ModelConfig, key: Array, dtype) -> Dict[str, Array]:
    D, di, N, nh, W = (
        cfg.d_model,
        cfg.d_inner,
        cfg.ssm_state,
        cfg.n_ssm_heads,
        cfg.ssm_conv_width,
    )
    keys = jax.random.split(key, 4)
    s = D ** -0.5
    # in_proj emits [z (di), x (di), B (N), C (N), dt (nh)]
    p = {
        "in_proj": (jax.random.normal(keys[0], (D, 2 * di + 2 * N + nh)) * s).astype(dtype),
        "conv_w": (jax.random.normal(keys[1], (W, di + 2 * N)) * (W ** -0.5)).astype(dtype),
        "conv_b": jnp.zeros((di + 2 * N,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nh)).astype(jnp.float32),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(jnp.full((nh,), 1e-2))).astype(jnp.float32),
        "norm": jnp.zeros((di,), dtype),
        "out_proj": (jax.random.normal(keys[2], (di, D)) * (di ** -0.5)).astype(dtype),
    }
    return p


def _split_proj(cfg: ModelConfig, zxbcdt: Array):
    di, N, nh = cfg.d_inner, cfg.ssm_state, cfg.n_ssm_heads
    z, xBC, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * N], axis=-1)
    return z, xBC, dt


# --------------------------------------------------------------------------
# Chunked SSD forward (training / prefill)
# --------------------------------------------------------------------------
def _segsum(a: Array) -> Array:
    """a: (..., T) log-decays -> (..., T, T) lower-triangular segment sums."""
    T = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    i = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
    return jnp.where(i >= j, diff, -jnp.inf)


@jax.named_scope("ssd")
def ssd_chunked(
    x: Array,  # (B, S, nh, hd)  (already multiplied by dt)
    a: Array,  # (B, S, nh)      log-decay = dt * A  (negative)
    Bm: Array,  # (B, S, N)
    Cm: Array,  # (B, S, N)
    chunk: int,
    h0: Optional[Array] = None,  # (B, nh, hd, N)
) -> Tuple[Array, Array]:
    """Returns (y: (B,S,nh,hd), final_state: (B,nh,hd,N))."""
    B_, S, nh, hd = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    nC = S // Q
    assert nC * Q == S, f"seq {S} not divisible by ssm chunk {Q}"
    xc = x.reshape(B_, nC, Q, nh, hd)
    ac = a.reshape(B_, nC, Q, nh).transpose(0, 3, 1, 2)  # (B, nh, nC, Q)
    Bc = Bm.reshape(B_, nC, Q, N)
    Cc = Cm.reshape(B_, nC, Q, N)

    a_cumsum = jnp.cumsum(ac, axis=-1)  # (B, nh, nC, Q)

    # 1. intra-chunk (diagonal blocks): quadratic within the chunk.
    L = jnp.exp(_segsum(ac))  # (B, nh, nC, Q, Q)
    y_diag = jnp.einsum("bcln,bcsn,bhcls,bcshp->bclhp", Cc, Bc, L, xc)

    # 2. per-chunk input -> end-of-chunk state contribution.
    decay_states = jnp.exp(a_cumsum[..., -1:] - a_cumsum)  # (B, nh, nC, Q)
    states = jnp.einsum("bcln,bhcl,bclhp->bchpn", Bc, decay_states, xc)

    # 3. inter-chunk recurrence, carried linearly with lax.scan.
    # The state is fp32 regardless of the compute dtype: long products of
    # decays are exactly the kind of accumulation bf16 cannot carry.
    chunk_decay = jnp.exp(a_cumsum[..., -1])  # (B, nh, nC)
    if h0 is None:
        h0 = jnp.zeros((B_, nh, hd, N), jnp.float32)
    h0 = h0.astype(jnp.float32)

    def step(h, inp):
        st, dec = inp  # st: (B, nh, hd, N); dec: (B, nh)
        h_in = h  # state *entering* this chunk
        h = h * dec[..., None, None] + st.astype(jnp.float32)
        return h, h_in

    sts = states.transpose(1, 0, 2, 3, 4)  # (nC, B, nh, hd, N)
    decs = chunk_decay.transpose(2, 0, 1)  # (nC, B, nh)
    h_final, h_ins = jax.lax.scan(step, h0, (sts, decs))

    # 4. state -> output within each chunk.
    state_decay_out = jnp.exp(a_cumsum)  # (B, nh, nC, Q)
    y_off = jnp.einsum(
        "bcln,bchpn,bhcl->bclhp", Cc, h_ins.transpose(1, 0, 2, 3, 4), state_decay_out
    )
    y = (y_diag + y_off).reshape(B_, S, nh, hd).astype(x.dtype)
    return y, h_final


def _conv1d(xBC: Array, w: Array, b: Array) -> Array:
    """Depthwise causal conv, width W: (B, S, C) with (W, C) filters."""
    W = w.shape[0]
    pad = jnp.pad(xBC, ((0, 0), (W - 1, 0), (0, 0)))
    out = sum(
        pad[:, i : i + xBC.shape[1], :] * w[i][None, None, :] for i in range(W)
    )
    return out + b[None, None, :]


@jax.named_scope("mamba")
def mamba_apply(
    cfg: ModelConfig,
    p,
    x: Array,
    h0: Optional[Array] = None,
    *,
    return_conv_tail: bool = False,
):
    """Full-sequence forward.  x: (B, S, D) -> (B, S, D), final ssm state.

    ``return_conv_tail`` additionally returns the last W-1 pre-conv
    activations, which seed the rolling conv window when a prefill hands
    off to incremental decode."""
    B, S, D = x.shape
    di, N, nh, hd = cfg.d_inner, cfg.ssm_state, cfg.n_ssm_heads, cfg.ssm_head_dim
    zxbcdt = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    z, xBC_pre, dt = _split_proj(cfg, zxbcdt)
    xBC = jax.nn.silu(_conv1d(xBC_pre, p["conv_w"], p["conv_b"]))
    xs, Bm, Cm = jnp.split(xBC, [di, di + N], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B,S,nh)
    A = -jnp.exp(p["A_log"])  # (nh,)
    xh = xs.reshape(B, S, nh, hd)
    y, h = ssd_chunked(
        xh * dt[..., None].astype(xh.dtype),
        dt * A,  # log decay
        Bm,
        Cm,
        cfg.ssm_chunk,
        h0,
    )
    y = y + xh * p["D"][None, None, :, None].astype(xh.dtype)
    y = y.reshape(B, S, di).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["norm"])
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"]).astype(x.dtype)
    if return_conv_tail:
        W = cfg.ssm_conv_width
        return out, h, xBC_pre[:, S - (W - 1) :, :]
    return out, h


# --------------------------------------------------------------------------
# Recurrent decode (O(1) per token)
# --------------------------------------------------------------------------
def mamba_state_init(cfg: ModelConfig, batch: int, dtype) -> Dict[str, Array]:
    di, N, nh, hd, W = (
        cfg.d_inner,
        cfg.ssm_state,
        cfg.n_ssm_heads,
        cfg.ssm_head_dim,
        cfg.ssm_conv_width,
    )
    return {
        "h": jnp.zeros((batch, nh, hd, N), jnp.float32),  # fp32 SSM state
        "conv": jnp.zeros((batch, W - 1, di + 2 * N), dtype),
    }


def mamba_decode_step(
    cfg: ModelConfig, p, x: Array, state: Dict[str, Array]
) -> Tuple[Array, Dict[str, Array]]:
    """x: (B, 1, D) -> (B, 1, D) with updated state."""
    B = x.shape[0]
    di, N, nh, hd = cfg.d_inner, cfg.ssm_state, cfg.n_ssm_heads, cfg.ssm_head_dim
    zxbcdt = jnp.einsum("bsd,de->bse", x, p["in_proj"])[:, 0]  # (B, E)
    z, xBC, dt = _split_proj(cfg, zxbcdt)
    # rolling conv window
    window = jnp.concatenate([state["conv"], xBC[:, None, :]], axis=1)  # (B,W,C)
    conv_out = (
        jnp.einsum("bwc,wc->bc", window, p["conv_w"]) + p["conv_b"][None, :]
    )
    xBC = jax.nn.silu(conv_out)
    xs, Bm, Cm = jnp.split(xBC, [di, di + N], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B, nh)
    A = -jnp.exp(p["A_log"])
    dA = jnp.exp(dt * A)  # (B, nh)
    xh = xs.reshape(B, nh, hd)
    h = state["h"].astype(jnp.float32)
    h = h * dA[..., None, None] + jnp.einsum(
        "bhp,bn->bhpn", (dt[..., None].astype(xh.dtype)) * xh, Bm
    ).astype(jnp.float32)
    y = jnp.einsum("bhpn,bn->bhp", h, Cm.astype(jnp.float32))
    y = y.astype(x.dtype).reshape(B, nh, hd) + xh * p["D"][None, :, None].astype(xh.dtype)
    y = y.reshape(B, di)
    y = rms_norm(y * jax.nn.silu(z), p["norm"])
    out = jnp.einsum("be,ed->bd", y, p["out_proj"]).astype(x.dtype)[:, None, :]
    return out, {"h": h, "conv": window[:, 1:, :]}
