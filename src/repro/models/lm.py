"""Decoder-only language model: dense / MoE / SSM / hybrid / VLM families.

One composition handles 9 of the 10 assigned architectures (seamless-m4t is
in encdec.py).  Layers are stacked and driven by ``jax.lax.scan`` so the
64-layer configs lower to compact HLO; per-layer heterogeneity (gemma's
local:global attention pattern, zamba2's shared-attention insertions) is
expressed with per-layer flag vectors scanned alongside the parameters.

The zamba2 hybrid: a *single* shared attention+MLP block (its params live
outside the scan) is applied after every ``hybrid_period``-th Mamba layer
via ``lax.cond``; each application gets its own KV cache slot in decode.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import (
    attn_apply,
    attn_decode_apply,
    attn_init,
    mlp_apply,
    mlp_init,
    rms_norm,
    softcap,
)
from .mamba2 import (
    mamba_apply,
    mamba_decode_step,
    mamba_init,
    mamba_state_init,
)
from .moe import moe_apply, moe_init
from .sharding import constrain_residual

Array = jax.Array


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _cast_block(p, dtype):
    """Cast one layer's param slice to the compute dtype *inside* the scan
    body: the cast precedes any GSPMD-inserted weight gather, so FSDP
    all-gathers move bf16, not fp32 masters."""
    return jax.tree.map(
        lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) else a,
        p,
    )


def _stacked_init(fn, n: int, key: Array):
    return jax.vmap(fn)(jax.random.split(key, n))


class LM:
    """Pure-functional model bundle for one config."""

    def __init__(self, cfg: ModelConfig):
        assert cfg.family in ("dense", "moe", "ssm", "hybrid", "vlm")
        self.cfg = cfg

    # ------------------------------------------------------------------
    # Init
    # ------------------------------------------------------------------
    def init(self, key: Array) -> Dict[str, Any]:
        cfg = self.cfg
        dt = _dtype(cfg)
        keys = jax.random.split(key, 8)
        params: Dict[str, Any] = {
            "embed": (
                jax.random.normal(keys[0], (cfg.vocab, cfg.d_model)) * 0.02
            ).astype(dt),
            "final_norm": jnp.zeros((cfg.d_model,), dt),
        }
        if not cfg.tie_embeddings:
            params["unembed"] = (
                jax.random.normal(keys[1], (cfg.d_model, cfg.vocab)) * 0.02
            ).astype(dt)

        L = cfg.n_layers
        if cfg.family in ("ssm", "hybrid"):
            params["blocks"] = _stacked_init(
                lambda k: self._ssm_block_init(k), L, keys[2]
            )
        else:
            params["blocks"] = _stacked_init(
                lambda k: self._attn_block_init(k), L, keys[2]
            )
        if cfg.family == "hybrid":
            params["shared"] = self._attn_block_init(keys[3], force_dense=True)
        return params

    def _attn_block_init(self, key: Array, force_dense: bool = False):
        cfg, dt = self.cfg, _dtype(self.cfg)
        k1, k2 = jax.random.split(key)
        p = {
            "ln1": jnp.zeros((cfg.d_model,), dt),
            "ln2": jnp.zeros((cfg.d_model,), dt),
            "attn": attn_init(cfg, k1, dt),
        }
        if cfg.post_norm:
            p["ln1_post"] = jnp.zeros((cfg.d_model,), dt)
            p["ln2_post"] = jnp.zeros((cfg.d_model,), dt)
        if cfg.family == "moe" and not force_dense:
            p["moe"] = moe_init(cfg, k2, dt)
        else:
            p["mlp"] = mlp_init(cfg, k2, dt)
        return p

    def _ssm_block_init(self, key: Array):
        cfg, dt = self.cfg, _dtype(self.cfg)
        return {
            "ln1": jnp.zeros((cfg.d_model,), dt),
            "mamba": mamba_init(cfg, key, dt),
        }

    # ------------------------------------------------------------------
    # Layer bodies
    # ------------------------------------------------------------------
    def _attn_block_apply(self, p, x: Array, is_local: Any, positions=None):
        cfg = self.cfg
        with jax.named_scope("attn"):
            h = attn_apply(
                cfg, p["attn"], rms_norm(x, p["ln1"]), is_local=is_local, positions=positions
            )
            if cfg.post_norm:
                h = rms_norm(h, p["ln1_post"])
            x = x + h
        with jax.named_scope("mlp"):
            h2_in = rms_norm(x, p["ln2"])
            aux = {}
            if "moe" in p:
                h2, aux = moe_apply(cfg, p["moe"], h2_in)
            else:
                h2 = mlp_apply(cfg, p["mlp"], h2_in)
            if cfg.post_norm:
                h2 = rms_norm(h2, p["ln2_post"])
            return x + h2, aux

    # ------------------------------------------------------------------
    # Forward (train / prefill): returns final hidden states + aux
    # ------------------------------------------------------------------
    def hidden_states(
        self, params, tokens: Array, *, remat: bool = True
    ) -> Tuple[Array, Dict[str, Array]]:
        cfg = self.cfg
        with jax.named_scope("embed"):
            x = params["embed"][tokens].astype(_dtype(cfg))
            if cfg.emb_scale_by_sqrt_dim:
                x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)

        if cfg.family in ("ssm", "hybrid"):
            x = self._ssm_stack(params, x, remat=remat)
            aux = {}
        else:
            x, aux = self._attn_stack(params, x, remat=remat)
        with jax.named_scope("head"):
            return rms_norm(x, _cast_block(params["final_norm"], x.dtype)), aux

    def _attn_stack(self, params, x: Array, *, remat: bool):
        cfg = self.cfg
        flags = jnp.asarray(cfg.local_flags(), dtype=bool)

        def body(x, inp):
            p, flag = inp
            p = _cast_block(p, x.dtype)
            y, aux = self._attn_block_apply(p, x, flag)
            y = constrain_residual(cfg, y)
            return y, aux

        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        with jax.named_scope("layers"):
            x, auxs = jax.lax.scan(body, x, (params["blocks"], flags))
        aux = {k: jnp.mean(v) for k, v in auxs.items()} if auxs else {}
        return x, aux

    def _ssm_stack(self, params, x: Array, *, remat: bool):
        cfg = self.cfg
        L = cfg.n_layers
        if cfg.family == "hybrid" and cfg.hybrid_period:
            flags = jnp.asarray(
                [(i % cfg.hybrid_period) == cfg.hybrid_period - 1 for i in range(L)]
            )
        else:
            flags = jnp.zeros((L,), bool)
        shared = params.get("shared")

        def body(x, inp):
            p, flag = inp
            p = _cast_block(p, x.dtype)
            h, _ = mamba_apply(cfg, p["mamba"], rms_norm(x, p["ln1"]))
            x = x + h
            x = constrain_residual(cfg, x)

            if shared is not None:
                def with_attn(x):
                    with jax.named_scope("shared_block"):
                        y, _ = self._attn_block_apply(
                            _cast_block(shared, x.dtype), x, is_local=False
                        )
                        return y.astype(x.dtype)

                x = jax.lax.cond(flag, with_attn, lambda x: x, x)
            return x, None

        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        with jax.named_scope("layers"):
            x, _ = jax.lax.scan(body, x, (params["blocks"], flags))
        return x

    # ------------------------------------------------------------------
    # Prefill: full forward that also fills the decode caches
    # ------------------------------------------------------------------
    def prefill(self, params, tokens: Array, max_len: Optional[int] = None):
        """Returns (last-position logits (B,1,V), decode state)."""
        cfg = self.cfg
        B, S = tokens.shape
        max_len = max_len or S
        dt = _dtype(cfg)
        x = params["embed"][tokens]
        if cfg.emb_scale_by_sqrt_dim:
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)

        state: Dict[str, Any] = {"pos": jnp.full((B,), S, jnp.int32)}

        def pad_kv(k):  # (L, B, S, K, hd) -> (L, B, max_len, K, hd)
            if max_len == S:
                return k
            pad = [(0, 0)] * k.ndim
            pad[2] = (0, max_len - S)
            return jnp.pad(k, pad)

        if cfg.family in ("ssm", "hybrid"):
            x, state = self._ssm_prefill(params, state, x, max_len)
        else:
            flags = jnp.asarray(cfg.local_flags(), dtype=bool)

            def body(x, inp):
                p, flag = inp
                h, kv = attn_apply(
                    cfg, p["attn"], rms_norm(x, p["ln1"]), is_local=flag,
                    return_kv=True,
                )
                if cfg.post_norm:
                    h = rms_norm(h, p["ln1_post"])
                x = x + h
                h2_in = rms_norm(x, p["ln2"])
                if "moe" in p:
                    h2, _ = moe_apply(cfg, p["moe"], h2_in)
                else:
                    h2 = mlp_apply(cfg, p["mlp"], h2_in)
                if cfg.post_norm:
                    h2 = rms_norm(h2, p["ln2_post"])
                return x + h2, (kv[0].astype(dt), kv[1].astype(dt))

            x, (ks, vs) = jax.lax.scan(body, x, (params["blocks"], flags))
            state["kv"] = (pad_kv(ks), pad_kv(vs))

        hidden = rms_norm(x[:, -1:], params["final_norm"])
        return self.logits(params, hidden), state

    def _ssm_prefill(self, params, state, x, max_len: int):
        cfg = self.cfg
        dt = _dtype(cfg)
        B, S, _ = x.shape
        L, W = cfg.n_layers, cfg.ssm_conv_width

        def mamba_body(x, p):
            h, hstate, tail = mamba_apply(
                cfg, p["mamba"], rms_norm(x, p["ln1"]), return_conv_tail=True
            )
            return x + h, (hstate, tail)

        if cfg.family == "ssm" or not cfg.hybrid_period:
            x, (hs, tails) = jax.lax.scan(mamba_body, x, params["blocks"])
            state["ssm"] = {"h": hs, "conv": tails.astype(dt)}
            return x, state

        # Hybrid: python loop over shared-attention segments so each
        # invocation's KV cache is collected without 38x transient caches.
        period = cfg.hybrid_period
        n_inv = L // period
        shared = params["shared"]
        K, hd = cfg.n_kv_heads, cfg.head_dim
        ks_list, vs_list, hs_list, tails_list = [], [], [], []
        start = 0
        for inv in range(n_inv + 1):
            stop = min(start + period, L)
            if stop > start:
                seg = jax.tree.map(lambda p: p[start:stop], params["blocks"])
                x, (hs, tails) = jax.lax.scan(mamba_body, x, seg)
                hs_list.append(hs)
                tails_list.append(tails)
            if inv < n_inv:
                h, kv = attn_apply(
                    cfg, shared["attn"], rms_norm(x, shared["ln1"]), return_kv=True
                )
                x = x + h
                x = x + mlp_apply(cfg, shared["mlp"], rms_norm(x, shared["ln2"]))
                ks_list.append(kv[0].astype(dt))
                vs_list.append(kv[1].astype(dt))
            start = stop

        def pad(k):  # (B, S, K, hd) -> (B, max_len, K, hd)
            if max_len == S:
                return k
            return jnp.pad(k, ((0, 0), (0, max_len - S), (0, 0), (0, 0)))

        state["ssm"] = {
            "h": jnp.concatenate(hs_list, axis=0),
            "conv": jnp.concatenate(tails_list, axis=0).astype(dt),
        }
        state["shared_kv"] = (
            jnp.stack([pad(k) for k in ks_list]),
            jnp.stack([pad(v) for v in vs_list]),
        )
        return x, state

    def logits(self, params, hidden: Array) -> Array:
        cfg = self.cfg
        w = params.get("unembed")
        if w is None:
            w = params["embed"].T
        with jax.named_scope("head"):
            out = jnp.einsum("bsd,dv->bsv", hidden, w).astype(jnp.float32)
            return softcap(out, cfg.final_logit_softcap)

    def apply(self, params, tokens: Array, *, remat: bool = False) -> Array:
        hidden, _ = self.hidden_states(params, tokens, remat=remat)
        return self.logits(params, hidden)

    # ------------------------------------------------------------------
    # Decode (one token, persistent cache)
    # ------------------------------------------------------------------
    def decode_init(self, batch: int, max_len: int) -> Dict[str, Any]:
        cfg = self.cfg
        dt = _dtype(cfg)
        L = cfg.n_layers
        state: Dict[str, Any] = {"pos": jnp.zeros((batch,), jnp.int32)}
        if cfg.family in ("ssm", "hybrid"):
            state["ssm"] = jax.vmap(
                lambda _: mamba_state_init(cfg, batch, dt)
            )(jnp.arange(L))
            if cfg.family == "hybrid" and cfg.hybrid_period:
                n_inv = cfg.n_layers // cfg.hybrid_period
                K, hd = cfg.n_kv_heads, cfg.head_dim
                state["shared_kv"] = (
                    jnp.zeros((n_inv, batch, max_len, K, hd), dt),
                    jnp.zeros((n_inv, batch, max_len, K, hd), dt),
                )
        else:
            K, hd = cfg.n_kv_heads, cfg.head_dim
            state["kv"] = (
                jnp.zeros((L, batch, max_len, K, hd), dt),
                jnp.zeros((L, batch, max_len, K, hd), dt),
            )
        return state

    def decode_step(self, params, state, tokens: Array):
        """tokens: (B, 1) -> (logits (B, 1, V), new state)."""
        cfg = self.cfg
        pos = state["pos"]
        x = params["embed"][tokens]
        if cfg.emb_scale_by_sqrt_dim:
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)

        if cfg.family in ("ssm", "hybrid"):
            x, state = self._ssm_decode(params, state, x, pos)
        else:
            flags = jnp.asarray(cfg.local_flags(), dtype=bool)

            def body(x, inp):
                p, kv, flag = inp
                h, kv = attn_decode_apply(
                    cfg, p["attn"], rms_norm(x, p["ln1"]), kv, pos, is_local=flag
                )
                if cfg.post_norm:
                    h = rms_norm(h, p["ln1_post"])
                x = x + h
                h2_in = rms_norm(x, p["ln2"])
                if "moe" in p:
                    h2, _ = moe_apply(cfg, p["moe"], h2_in, dropless=True)
                else:
                    h2 = mlp_apply(cfg, p["mlp"], h2_in)
                if cfg.post_norm:
                    h2 = rms_norm(h2, p["ln2_post"])
                return x + h2, kv

            x, new_kv = jax.lax.scan(body, x, (params["blocks"], state["kv"], flags))
            state = {**state, "kv": new_kv}

        hidden = rms_norm(x, params["final_norm"])
        logits = self.logits(params, hidden)
        state = {**state, "pos": pos + 1}
        return logits, state

    def _ssm_decode(self, params, state, x, pos):
        cfg = self.cfg
        L = cfg.n_layers
        if cfg.family == "hybrid" and cfg.hybrid_period:
            flags = jnp.asarray(
                [(i % cfg.hybrid_period) == cfg.hybrid_period - 1 for i in range(L)]
            )
        else:
            flags = jnp.zeros((L,), bool)
        shared = params.get("shared")
        shared_kv = state.get("shared_kv")

        def body(carry, inp):
            x, inv_idx, skv = carry
            p, ssm, flag = inp
            h, new_ssm = mamba_decode_step(cfg, p["mamba"], rms_norm(x, p["ln1"]), ssm)
            x = x + h

            if shared is not None and skv is not None:
                def with_attn(op):
                    x, inv_idx, skv = op
                    kv = (skv[0][inv_idx], skv[1][inv_idx])
                    h, (nk, nv) = attn_decode_apply(
                        cfg, shared["attn"], rms_norm(x, shared["ln1"]), kv, pos
                    )
                    x = x + h
                    h2 = mlp_apply(cfg, shared["mlp"], rms_norm(x, shared["ln2"]))
                    x = x + h2
                    skv = (
                        jax.lax.dynamic_update_index_in_dim(skv[0], nk, inv_idx, 0),
                        jax.lax.dynamic_update_index_in_dim(skv[1], nv, inv_idx, 0),
                    )
                    return x, inv_idx + 1, skv

                x, inv_idx, skv = jax.lax.cond(
                    flag, with_attn, lambda op: op, (x, inv_idx, skv)
                )
            return (x, inv_idx, skv), new_ssm

        carry0 = (x, jnp.int32(0), shared_kv)
        (x, _, new_skv), new_ssm = jax.lax.scan(
            body, carry0, (params["blocks"], state["ssm"], flags)
        )
        state = {**state, "ssm": new_ssm}
        if new_skv is not None:
            state["shared_kv"] = new_skv
        return x, state
