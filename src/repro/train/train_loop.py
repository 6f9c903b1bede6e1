"""Training step assembly: loss, grads, optimizer, metrics.

``make_train_step`` builds the pure jit-able function the launcher (and
the multi-pod dry-run) lowers.  Parameters are fp32 masters; the forward
runs in the model dtype (bf16 on TPU).  Per-layer remat is on inside the
model's scan.  ``TrainState`` is a plain pytree so checkpointing and
sharding rules apply uniformly.

Device operations carry fixed ``jax.named_scope`` names in their
``op_name`` metadata, so a profile can say which layer spent the step:
``embed``, ``layers`` (the layer scan), ``mamba`` and ``ssd`` within it,
``shared_block`` with ``attn`` and ``mlp`` (zamba2's shared block),
``attn`` and ``mlp`` in attention layers, ``head`` (final norm, logits,
loss) and ``optimizer``.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import get_model
from repro.models.config import ModelConfig
from repro.train import optimizer as opt
from repro.train.losses import chunked_xent

Array = jax.Array


class TrainState(NamedTuple):
    params: Any  # fp32 masters
    opt: opt.AdamState
    step: Array


def init_state(cfg: ModelConfig, ocfg: opt.OptConfig, key: Array) -> TrainState:
    model = get_model(cfg)
    params = model.init(key)
    params = jax.tree.map(
        lambda p: p.astype(jnp.float32) if jnp.issubdtype(p.dtype, jnp.floating) else p,
        params,
    )
    return TrainState(params=params, opt=opt.init(ocfg, params), step=jnp.zeros((), jnp.int32))


def _cast(params: Any, dtype) -> Any:
    return jax.tree.map(
        lambda p: p.astype(dtype) if jnp.issubdtype(p.dtype, jnp.floating) else p,
        params,
    )


def make_loss_fn(cfg: ModelConfig):
    model = get_model(cfg)

    def loss_fn(params, batch: Dict[str, Array]):
        # No up-front cast: layer blocks cast their own slice inside the
        # scan (convert-before-gather); only the embedding table is cast
        # at its two use sites.
        if cfg.family == "encdec":
            hidden, aux = model.hidden_states(params, batch, remat=True)
        else:
            hidden, aux = model.hidden_states(params, batch["tokens"], remat=True)
        loss, metrics = chunked_xent(
            cfg, params, hidden, batch["targets"], batch.get("loss_mask")
        )
        if "moe_lb_loss" in aux:
            loss = loss + 0.01 * aux["moe_lb_loss"]
        metrics.update({k: v for k, v in aux.items()})
        return loss, metrics

    return loss_fn


def make_train_step(
    cfg: ModelConfig,
    ocfg: opt.OptConfig,
    *,
    microbatches: int = 1,
    grad_specs: Any = None,
):
    """``microbatches > 1`` scans gradient accumulation over batch slices —
    the activation-memory knob for the XXL configs.  ``grad_specs`` (a
    PartitionSpec pytree congruent to params) pins accumulated gradients
    to the parameter sharding, forcing the per-microbatch reduce-scatter
    instead of replicated full-size gradient buffers."""
    loss_fn = make_loss_fn(cfg)

    def to_bf16(t):
        return jax.tree.map(
            lambda g: g.astype(jnp.bfloat16)
            if jnp.issubdtype(g.dtype, jnp.floating)
            else g,
            t,
        )

    def pin(grads):
        if grad_specs is None:
            return grads
        return jax.tree.map(
            lambda g, s: jax.lax.with_sharding_constraint(g, s), grads, grad_specs
        )

    def grad_fn(params, batch):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch
        )
        # bf16 gradient reduction: the cross-device grad sum moves half
        # the bytes; the optimizer re-widens to fp32 shard-locally.
        return loss, metrics, pin(to_bf16(grads))

    def train_step(state: TrainState, batch: Dict[str, Array]):
        if microbatches <= 1:
            loss, metrics, grads = grad_fn(state.params, batch)
        else:
            def slice_mb(i, x):
                mb = x.shape[0] // microbatches
                return jax.lax.dynamic_slice_in_dim(x, i * mb, mb, 0)

            def body(acc, i):
                mb_batch = {k: slice_mb(i, v) for k, v in batch.items()}
                loss, metrics, grads = grad_fn(state.params, mb_batch)
                acc_g, acc_loss = acc
                acc_g = jax.tree.map(lambda a, g: a + g, acc_g, grads)
                return (acc_g, acc_loss + loss), metrics

            zero = pin(
                jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.bfloat16)
                    if jnp.issubdtype(p.dtype, jnp.floating)
                    else jnp.zeros(p.shape, p.dtype),
                    state.params,
                )
            )
            (grads, loss_sum), metricss = jax.lax.scan(
                body, (zero, jnp.zeros((), jnp.float32)), jnp.arange(microbatches)
            )
            grads = pin(
                jax.tree.map(lambda g: (g / microbatches).astype(g.dtype), grads)
            )
            loss = loss_sum / microbatches
            metrics = {k: jnp.mean(v) for k, v in metricss.items()}

        with jax.named_scope("optimizer"):
            new_params, new_opt, opt_metrics = opt.update(
                ocfg, state.params, grads, state.opt
            )
        metrics = {**metrics, **opt_metrics, "loss": loss}
        return TrainState(new_params, new_opt, state.step + 1), metrics

    return train_step


def make_eval_step(cfg: ModelConfig):
    loss_fn = make_loss_fn(cfg)

    def eval_step(params, batch):
        loss, metrics = loss_fn(params, batch)
        return {**metrics, "loss": loss}

    return eval_step
