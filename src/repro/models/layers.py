"""Shared neural building blocks, pure-functional JAX.

Everything is einsum-based so GSPMD sharding propagates cleanly from the
parameter PartitionSpecs (models/sharding.py).  Attention ships three
implementations:

  * ``naive``   — materialized (B,H,Sq,Sk) logits; smoke tests and oracles.
  * ``chunked`` — ``lax.scan`` over query chunks; peak memory O(Cq x Sk).
    This is the path the multi-pod dry-run lowers for the 32k shapes — it
    is the jnp statement of the same blocking the Pallas flash-attention
    kernel implements on TPU.
  * ``decode``  — single-token attention against a KV cache, with windowed
    reads for local (sliding-window) layers.

``attention`` picks one (``_attention_path``); under ``attn_impl="auto"``
causal multi-head attention on one TPU chip takes the fused splash
kernels (kernels/ops.py ``fused_causal_attention``) instead.

Masks are built from ``broadcasted_iota`` — never materialized constants —
so a 32k x 32k causal mask costs nothing at trace time.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig

Array = jax.Array
NEG_INF = -2.0e38  # large-negative fill that survives bf16/fp32 softmax


# --------------------------------------------------------------------------
# Elementary ops
# --------------------------------------------------------------------------
def rms_norm(x: Array, scale: Array, eps: float = 1e-6) -> Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def softcap(x: Array, cap: Optional[float]) -> Array:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


def activation_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": partial(jax.nn.gelu, approximate=True)}[name]


# --------------------------------------------------------------------------
# Rotary position embeddings
# --------------------------------------------------------------------------
def rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freq  # (..., S, half)
    sin = jnp.sin(angles)[..., None, :]  # (..., S, 1, half)
    cos = jnp.cos(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Attention
# --------------------------------------------------------------------------
def _mask_bias(
    q_pos: Array,  # (Sq,)
    k_pos: Array,  # (Sk,)
    *,
    causal: bool,
    window: Optional[Any],  # None | int | traced scalar (None disables)
    is_local: Any = True,  # static bool or traced scalar
) -> Array:
    """Additive bias (Sq, Sk): 0 where attendable, NEG_INF elsewhere."""
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), dtype=bool)
    if causal:
        ok &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        in_window = (q_pos[:, None] - k_pos[None, :]) < window
        local = jnp.asarray(is_local)
        ok &= in_window | ~local
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _qk_scale(cfg: ModelConfig) -> float:
    return cfg.head_dim ** -0.5


def attention_naive(
    q: Array,  # (B, Sq, H, hd)
    k: Array,  # (B, Sk, K, hd)
    v: Array,  # (B, Sk, K, hd)
    *,
    cfg: ModelConfig,
    q_offset: Any = 0,
    causal: bool = True,
    is_local: Any = False,
) -> Array:
    B, Sq, H, hd = q.shape
    Sk, K = k.shape[1], k.shape[2]
    rep = H // K
    qh = q.reshape(B, Sq, K, rep, hd)
    logits = jnp.einsum("bqkrd,bskd->bkrqs", qh, k).astype(jnp.float32)
    logits = logits * _qk_scale(cfg)
    logits = softcap(logits, cfg.attn_logit_softcap)
    q_pos = q_offset + jax.lax.broadcasted_iota(jnp.int32, (Sq,), 0)
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (Sk,), 0)
    logits += _mask_bias(
        q_pos, k_pos, causal=causal, window=cfg.sliding_window, is_local=is_local
    )
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkrqs,bskd->bqkrd", w, v)
    return out.reshape(B, Sq, H, hd)


def attention_chunked(
    q: Array,
    k: Array,
    v: Array,
    *,
    cfg: ModelConfig,
    q_offset: Any = 0,
    causal: bool = True,
    is_local: Any = False,
) -> Array:
    """Scan over query chunks; full keys per chunk (exact, memory-bounded)."""
    B, Sq, H, hd = q.shape
    Cq = min(cfg.attn_q_chunk, Sq)
    if Sq % Cq != 0:
        return attention_naive(
            q, k, v, cfg=cfg, q_offset=q_offset, causal=causal, is_local=is_local
        )
    n_chunks = Sq // Cq
    qc = q.reshape(B, n_chunks, Cq, H, hd).transpose(1, 0, 2, 3, 4)
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (k.shape[1],), 0)
    K = k.shape[2]
    rep = H // K

    def body(carry, inp):
        qi, idx = inp
        q_pos = q_offset + idx * Cq + jax.lax.broadcasted_iota(jnp.int32, (Cq,), 0)
        qh = qi.reshape(B, Cq, K, rep, hd)
        logits = jnp.einsum("bqkrd,bskd->bkrqs", qh, k).astype(jnp.float32)
        logits = logits * _qk_scale(cfg)
        logits = softcap(logits, cfg.attn_logit_softcap)
        logits += _mask_bias(
            q_pos, k_pos, causal=causal, window=cfg.sliding_window, is_local=is_local
        )
        w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        out = jnp.einsum("bkrqs,bskd->bqkrd", w, v).reshape(B, Cq, H, hd)
        return carry, out

    # Flash-attention backward semantics: never save the (Cq, Sk) softmax
    # weights across chunks — recompute them per chunk in the backward.
    body = jax.checkpoint(body, prevent_cse=False)
    _, outs = jax.lax.scan(body, None, (qc, jnp.arange(n_chunks)))
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, hd)


def attention_decode(
    q: Array,  # (B, 1, H, hd)
    k_cache: Array,  # (B, S, K, hd)
    v_cache: Array,  # (B, S, K, hd)
    pos: Array,  # (B,) current position (#valid entries)
    *,
    cfg: ModelConfig,
    is_local: Any = False,
) -> Array:
    """One-token attention over the cache.  Local layers restrict reads to
    the sliding window via masking (the cache layout stays uniform; the
    Pallas decode kernel additionally skips the masked blocks)."""
    B, _, H, hd = q.shape
    S, K = k_cache.shape[1], k_cache.shape[2]
    rep = H // K
    qh = q.reshape(B, K, rep, hd)
    logits = jnp.einsum("bkrd,bskd->bkrs", qh, k_cache).astype(jnp.float32)
    logits = logits * _qk_scale(cfg)
    logits = softcap(logits, cfg.attn_logit_softcap)
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (S,), 0)
    valid = k_pos[None, :] < pos[:, None]  # (B, S)
    if cfg.sliding_window is not None:
        in_window = k_pos[None, :] >= (pos[:, None] - cfg.sliding_window)
        local = jnp.asarray(is_local)
        valid &= in_window | ~local
    logits += jnp.where(valid, 0.0, NEG_INF)[:, None, None, :]
    w = jax.nn.softmax(logits, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkrs,bskd->bkrd", w, v_cache)
    return out.reshape(B, 1, H, hd)


def attention_fsdp_seqshard(
    q: Array,
    k: Array,
    v: Array,
    *,
    cfg: ModelConfig,
    causal: bool = True,
    is_local: Any = False,
    q_offset: Any = 0,
) -> Array:
    """Sequence-parallel attention under the fsdp policy: queries stay
    sharded over 'model' along the sequence; each device runs the local
    chunked attention against the (replicated) full K/V with its shard's
    position offset.  Expressed with shard_map so the q-chunk loop runs
    on *local* shapes instead of fighting the GSPMD partitioner."""
    try:
        mesh = jax.sharding.get_abstract_mesh()
    except Exception:  # pragma: no cover
        mesh = None
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes)) if mesh is not None else {}
    dp = tuple(a for a in ("pod", "data") if a in sizes)
    dp_n = 1
    for a in dp:
        dp_n *= sizes[a]
    B, Sq = q.shape[0], q.shape[1]
    if (
        not sizes
        or "model" not in sizes
        or Sq % sizes["model"] != 0
        or (dp and B % dp_n != 0)
    ):
        return attention_chunked(
            q, k, v, cfg=cfg, causal=causal, is_local=is_local, q_offset=q_offset
        )
    from jax.sharding import PartitionSpec as P

    b_ax = dp if dp else None
    qspec = P(b_ax, "model", None, None)
    kvspec = P(b_ax, None, None, None)

    def local_fn(ql, kl, vl, flag):
        off = jax.lax.axis_index("model") * ql.shape[1]
        return attention_chunked(
            ql, kl, vl, cfg=cfg, causal=causal, is_local=flag, q_offset=off
        )

    return jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(qspec, kvspec, kvspec, P()),
        out_specs=qspec,
    )(q, k, v, jnp.asarray(is_local))


def _static_bool(x: Any) -> Optional[bool]:
    """``x`` as a Python bool, or None where it is only known at run time."""
    try:
        return bool(x)
    except jax.errors.ConcretizationTypeError:
        return None


def _attention_path(
    q_shape: Tuple[int, ...],  # (B, Sq, H, hd)
    k_shape: Tuple[int, ...],  # (B, Sk, K, hd)
    cfg: ModelConfig,
    *,
    causal: bool,
    is_local: Optional[bool],  # None: decided at run time
    q_offset: Any,
    platform: str,
    devices: int,
) -> str:
    """Which implementation ``attention`` runs: ``fsdp`` (sequence-sharded
    chunked), ``fused`` (kernels/ops.py's splash kernels), ``chunked``,
    ``naive`` or ``pallas``.  ``attn_impl="auto"`` takes ``fused`` for
    causal multi-head self-attention with no window or softcap on one TPU
    chip (a Mosaic kernel cannot be partitioned by GSPMD) at a sequence
    that is a multiple of the kernels' blocks and head_dim a multiple of
    128; every other call keeps the chunked / naive choice."""
    from repro.kernels.ops import fused_blocks

    if cfg.sharding_policy == "fsdp":
        return "fsdp"
    if cfg.attn_impl != "auto":
        return cfg.attn_impl
    _, Sq, H, hd = q_shape
    Sk, K = k_shape[1], k_shape[2]
    if (
        platform == "tpu"
        and devices == 1
        and cfg.sharding_policy == "none"
        and causal
        and (cfg.sliding_window is None or is_local is False)
        and cfg.attn_logit_softcap is None
        and K == H
        and Sq == Sk
        and isinstance(q_offset, int)
        and q_offset == 0
        and Sq % 128 == 0
        and fused_blocks(Sq) is not None
        and hd % 128 == 0
    ):
        return "fused"
    return "chunked" if Sq > 2 * cfg.attn_q_chunk else "naive"


def attention(q, k, v, *, cfg: ModelConfig, **kw) -> Array:
    from repro import obs

    path = _attention_path(
        q.shape,
        k.shape,
        cfg,
        causal=kw.get("causal", True),
        is_local=_static_bool(kw.get("is_local", False)),
        q_offset=kw.get("q_offset", 0),
        platform=jax.default_backend(),
        devices=jax.device_count(),
    )
    obs.count_event(f"attention.{path}")
    if path == "fsdp":
        return attention_fsdp_seqshard(q, k, v, cfg=cfg, **kw)
    if path == "fused":
        from repro.kernels import ops as kops

        return kops.fused_causal_attention(q, k, v)
    if path == "chunked":
        return attention_chunked(q, k, v, cfg=cfg, **kw)
    if path == "pallas":
        # TPU path: is_local must be static here (on real hardware each
        # local/global layer group lowers its own kernel instance).
        from repro.kernels import ops as kops

        is_local = bool(kw.get("is_local", False))
        window = cfg.sliding_window if (cfg.sliding_window and is_local) else None
        return kops.flash_attention(
            q,
            k,
            v,
            scale=cfg.head_dim ** -0.5,
            causal=kw.get("causal", True),
            window=window,
            softcap=cfg.attn_logit_softcap,
        )
    return attention_naive(q, k, v, cfg=cfg, **kw)


# --------------------------------------------------------------------------
# Attention block (init + apply + decode)
# --------------------------------------------------------------------------
def attn_init(cfg: ModelConfig, key: Array, dtype) -> Dict[str, Array]:
    D, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = D ** -0.5
    p = {
        "wq": (jax.random.normal(k1, (D, H, hd)) * s).astype(dtype),
        "wk": (jax.random.normal(k2, (D, K, hd)) * s).astype(dtype),
        "wv": (jax.random.normal(k3, (D, K, hd)) * s).astype(dtype),
        "wo": (jax.random.normal(k4, (H, hd, D)) * s).astype(dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def attn_qkv(cfg: ModelConfig, p, x: Array, positions: Array):
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    k = jnp.einsum("bsd,dke->bske", x, p["wk"])
    v = jnp.einsum("bsd,dke->bske", x, p["wv"])
    if cfg.sharding_policy != "none":
        # Attention boundary resharding (policy-dependent): under tp the
        # heads ride 'model' and the sequence gathers (Megatron SP);
        # under fsdp the queries stay sequence-sharded and K/V gather —
        # without this the seq-sharded residual leaks into the attention
        # contraction as per-chunk partial-sum all-reduces.
        from .sharding import constrain_attn_qkv

        q, k, v = constrain_attn_qkv(cfg, q, k, v)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_apply(
    cfg: ModelConfig,
    p,
    x: Array,
    *,
    is_local: Any = False,
    causal: bool = True,
    positions: Optional[Array] = None,
    return_kv: bool = False,
):
    B, S, _ = x.shape
    if positions is None:
        positions = jax.lax.broadcasted_iota(jnp.int32, (B, S), 1)
    q, k, v = attn_qkv(cfg, p, x, positions)
    out = attention(q, k, v, cfg=cfg, causal=causal, is_local=is_local)
    y = jnp.einsum("bshe,hed->bsd", out, p["wo"])
    if return_kv:
        return y, (k, v)
    return y


def cross_attn_apply(cfg: ModelConfig, p, x: Array, memory: Array) -> Array:
    """Decoder cross-attention: queries from x, keys/values from memory."""
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    k = jnp.einsum("bsd,dke->bske", memory, p["wk"])
    v = jnp.einsum("bsd,dke->bske", memory, p["wv"])
    out = attention(q, k, v, cfg=cfg, causal=False, is_local=False)
    return jnp.einsum("bshe,hed->bsd", out, p["wo"])


def attn_decode_apply(
    cfg: ModelConfig,
    p,
    x: Array,  # (B, 1, D)
    kv: Tuple[Array, Array],  # caches (B, S, K, hd)
    pos: Array,  # (B,)
    *,
    is_local: Any = False,
):
    B = x.shape[0]
    q, k_new, v_new = attn_qkv(cfg, p, x, pos[:, None])
    k_cache, v_cache = kv
    # In-place cache update at `pos` (same position for the whole batch in
    # our serving engine; vmapped dynamic slices keep it general).
    def upd(cache, new):
        def one(c, n, pp):
            return jax.lax.dynamic_update_slice(c, n, (pp, 0, 0))

        return jax.vmap(one)(cache, new, pos)

    k_cache = upd(k_cache, k_new.astype(k_cache.dtype))
    v_cache = upd(v_cache, v_new.astype(v_cache.dtype))
    out = attention_decode(
        q, k_cache, v_cache, pos + 1, cfg=cfg, is_local=is_local
    )
    y = jnp.einsum("bshe,hed->bsd", out, p["wo"])
    return y, (k_cache, v_cache)


# --------------------------------------------------------------------------
# MLP block
# --------------------------------------------------------------------------
def mlp_init(cfg: ModelConfig, key: Array, dtype, d_ff: Optional[int] = None):
    D, F = cfg.d_model, d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    s_in, s_out = D ** -0.5, F ** -0.5
    p = {
        "w_in": (jax.random.normal(k1, (D, F)) * s_in).astype(dtype),
        "w_out": (jax.random.normal(k2, (F, D)) * s_out).astype(dtype),
    }
    if cfg.mlp_gated:
        p["w_gate"] = (jax.random.normal(k3, (D, F)) * s_in).astype(dtype)
    return p


def mlp_apply(cfg: ModelConfig, p, x: Array) -> Array:
    act = activation_fn(cfg.activation)
    h = jnp.einsum("bsd,df->bsf", x, p["w_in"])
    if cfg.mlp_gated:
        g = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
        h = act(g) * h
    else:
        h = act(h)
    return jnp.einsum("bsf,fd->bsd", h, p["w_out"])
