"""Elastic training: consensus-governed membership driving a live JAX loop.

``ElasticTrainer`` welds the three layers together:

  control plane   ClusterController (Matchmaker MultiPaxos on the
                  deterministic simulator) decides *who is in the
                  cluster* and *what is durable*;
  data plane      a real jit'd train step over a (pod, data) mesh built
                  from the live device set;
  data pipeline   index-based batches resharded to the live pod count
                  (train/data.py's sharding invariance).

Membership-change flow (the paper's zero-stall reconfiguration mapped to
training):

  1. Leader bumps round s -> s+1 with the new pod set's acceptor config
     (Matchmaking phase; steps keep committing in the old epoch —
     Optimization 1).
  2. The new config is active one round trip later (Phase-1 bypass:
     no step-commit ever stalls — Optimization 2).
  3. The trainer re-meshes: rebuilds the (pod, data) mesh over the new
     device groups and ``device_put``s the train state to the new
     shardings, then continues stepping in the new epoch.
  4. Old pods are released only after GC (Scenario 1/2/3) retires their
     acceptor configuration — for planned scale-downs that is a few
     simulated ms after the switch.

On this container "pods" are disjoint groups of XLA host devices; the
same code runs unchanged on real multi-pod slices where each group is a
pod's chips.

Tracing: the trainer and its controller share one tracer (``repro.obs``;
``obs.NULL`` until one is attached as ``trainer.tracer``).  Each step is
a ``step`` span holding ``batch``, ``dispatch`` (the jitted call),
``loss_sync`` (the wait for the loss), ``ledger.advance``,
``ledger.commit``, ``checkpoint`` and ``remesh``.
"""

from __future__ import annotations

import hashlib
import json
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.coord.control_plane import ClusterController
from repro.core.proposer import Options
from repro.models.config import ModelConfig
from repro.models.sharding import axis_sizes, batch_spec, named, param_specs
from repro.train import OptConfig, TrainState, checkpoint, init_state, make_train_step
from repro.train.data import DataConfig, TokenPipeline


def _widen(spec: P, leaf, mesh_axes: Dict[str, int]) -> P:
    """Widen the FSDP axis 'data' to ('pod','data') where divisible —
    ZeRO across the DCN axis for optimizer state."""
    total = mesh_axes.get("pod", 1) * mesh_axes.get("data", 1)
    out = []
    for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * (leaf.ndim - len(spec))):
        if ax == "data" and dim % total == 0 and "pod" in mesh_axes:
            out.append(("pod", "data"))
        else:
            out.append(ax)
    return P(*out)


def state_specs(
    cfg: ModelConfig, state: TrainState, mesh_axes: Dict[str, int], policy: str = "tp"
):
    """Specs for the full TrainState: params per policy, optimizer moments
    widened to ('pod','data') FSDP (ZeRO-1 across DCN)."""
    pspec = param_specs(cfg, state.params, mesh_axes, policy=policy)
    flat_spec = jax.tree.leaves(pspec, is_leaf=lambda x: isinstance(x, P))
    flat_par = jax.tree.leaves(state.params)
    wide_flat = [_widen(s, l, mesh_axes) for s, l in zip(flat_spec, flat_par)]
    pdef = jax.tree_util.tree_structure(state.params)
    wide = jax.tree_util.tree_unflatten(pdef, wide_flat)

    def opt_like(tree):
        if jax.tree_util.tree_structure(tree) == pdef:
            return wide
        # int8 optimizer state: q (*param_lead, nb, block) / s (..., nb, 1)
        # per param.  The spec must be CONGRUENT with the param spec (same
        # axes on the same leading dims; the param's last-dim axis moves to
        # the block-count dim when it still divides) — any other layout
        # forces an SPMD reshard between q/s and the gradients, which XLA
        # resolves by fully replicating 100B-param tensors ("involuntary
        # full rematerialization").

        def per_param(pspec, node):
            q = node["q"]
            base = tuple(pspec) + (None,) * (q.ndim - 1 - len(tuple(pspec)))
            last_ax = base[-1] if base else None
            if last_ax is not None:
                axes = last_ax if isinstance(last_ax, tuple) else (last_ax,)
                n = 1
                for a in axes:
                    n *= mesh_axes.get(a, 1)
                nb = q.shape[-2]
                if n <= 1 or nb % n != 0:
                    last_ax = None
            lead = base[:-1] if base else ()
            qspec = P(*lead, last_ax, None)
            return {"q": qspec, "s": qspec}

        return jax.tree.map(
            per_param, wide, tree, is_leaf=lambda x: isinstance(x, P)
        )

    return TrainState(
        params=jax.tree_util.tree_unflatten(pdef, flat_spec),
        opt=type(state.opt)(
            m=opt_like(state.opt.m), v=opt_like(state.opt.v), step=P()
        ),
        step=P(),
    )


@dataclass
class ElasticConfig:
    checkpoint_dir: str = "/tmp/repro_ckpt"
    checkpoint_every: int = 10
    commit_every: int = 5  # ledger StepRecord cadence
    devices_per_pod: Optional[int] = None
    # Consensus knobs forwarded to the control plane's ClusterSpec
    # (e.g. Options(batch_max=16) to batch the ledger hot path).
    consensus_options: Optional[Options] = None


class ElasticTrainer:
    def __init__(
        self,
        cfg: ModelConfig,
        ocfg: OptConfig,
        dcfg: DataConfig,
        *,
        pods: Sequence[str],
        ecfg: Optional[ElasticConfig] = None,
        seed: int = 0,
    ):
        self.cfg, self.ocfg, self.dcfg = cfg, ocfg, dcfg
        self.ecfg = ecfg or ElasticConfig()
        self.pipeline = TokenPipeline(dcfg)
        self.controller = ClusterController(
            pods, seed=seed, options=self.ecfg.consensus_options
        )
        self.step_fn = make_train_step(cfg, ocfg)
        self._jitted: Dict[Tuple[int, int], Any] = {}

        self.state = init_state(cfg, ocfg, jax.random.PRNGKey(seed))
        self.step = 0
        self.epoch = 0
        self.mesh: Optional[Mesh] = None
        self.losses: List[float] = []
        self.events: List[Dict[str, Any]] = []
        self._remesh(list(pods))

    # ------------------------------------------------------------------
    def _device_groups(self, pods: List[str]) -> np.ndarray:
        devs = jax.devices()
        if len(devs) < len(pods):
            # Oversubscribed (single-device CI): membership stays logical —
            # the control plane, pipeline sharding and checkpoints all see
            # the pod set; the mesh collapses onto the available device.
            return np.array(devs[:1]).reshape(1, 1)
        per = self.ecfg.devices_per_pod or max(1, len(devs) // max(len(pods), 1))
        need = per * len(pods)
        assert need <= len(devs), f"need {need} devices, have {len(devs)}"
        return np.array(devs[:need]).reshape(len(pods), per)

    @property
    def tracer(self):
        """The tracer this trainer and its controller share (``obs.NULL``
        until one is attached)."""
        return self.controller.tracer

    @tracer.setter
    def tracer(self, tracer) -> None:
        self.controller.tracer = tracer

    def _remesh(self, pods: List[str]) -> None:
        with self.tracer.span("remesh"):
            groups = self._device_groups(pods)
            self.mesh = Mesh(groups, ("pod", "data"))
            maxes = axis_sizes(self.mesh)
            specs = state_specs(self.cfg, self.state, maxes)
            shardings = named(self.mesh, specs)
            self.state = jax.device_put(self.state, shardings)
            self._state_shardings = shardings
            self.pods = list(pods)
        self.events.append(
            {"t": "remesh", "step": self.step, "pods": list(pods), "devices": int(groups.size)}
        )

    def _batch(self) -> Dict[str, jnp.ndarray]:
        b = self.pipeline.jax_batch_at(self.step)
        maxes = axis_sizes(self.mesh)
        spec = batch_spec(self.cfg, b["tokens"].shape, maxes)
        sh = NamedSharding(self.mesh, spec)
        return {k: jax.device_put(v, sh) for k, v in b.items()}

    def _step_jit(self):
        key = (len(self.pods), id(self.mesh))
        if key not in self._jitted:
            # The new state keeps the mesh's shardings: the donated buffers
            # are reused in place and the next step hits the same program.
            self._jitted[key] = jax.jit(
                self.step_fn,
                donate_argnums=0,
                out_shardings=(self._state_shardings, None),
            )
        return self._jitted[key]

    # ------------------------------------------------------------------
    def run(self, n_steps: int) -> None:
        tr = self.tracer
        for _ in range(n_steps):
            with tr.step(self.step):
                with tr.span("batch"):
                    batch = self._batch()
                with tr.span("dispatch"):
                    self.state, metrics = self._step_jit()(self.state, batch)
                with tr.span("loss_sync"):
                    self.losses.append(float(metrics["loss"]))
                self.step += 1
                # advance the control plane "concurrently"
                with tr.span("ledger.advance"):
                    self.controller.sim.run_for(0.002)
                if self.step % self.ecfg.commit_every == 0:
                    self.controller.commit_step(self.step)
                if self.step % self.ecfg.checkpoint_every == 0:
                    self.save_checkpoint()
                # react to membership decided by the ledger
                epoch, pods = self.controller.membership()
                if epoch != self.epoch and pods:
                    self.epoch = epoch
                    self._remesh(list(pods))

    # ------------------------------------------------------------------
    def scale_to(self, pods: Sequence[str]) -> Dict[str, float]:
        """Planned elastic scale up/down (proactive reconfiguration)."""
        telemetry = self.controller.reconfigure(list(pods))
        self.events.append({"t": "scale", "step": self.step, **telemetry})
        return telemetry

    def fail_and_replace(self, dead: str, replacement: str) -> Dict[str, float]:
        self.controller.fail_pod(dead)
        new_pods = [p if p != dead else replacement for p in self.pods]
        telemetry = self.controller.reconfigure(new_pods)
        self.events.append({"t": "failover", "step": self.step, **telemetry})
        return telemetry

    # ------------------------------------------------------------------
    def save_checkpoint(self) -> None:
        t0 = time.perf_counter()
        with self.tracer.span("checkpoint"):
            man = checkpoint.save(
                self.ecfg.checkpoint_dir,
                self.step,
                self.state,
                meta={"arch": self.cfg.arch_id, "epoch": self.epoch},
            )
            digest = hashlib.sha256(
                json.dumps(man["files"], sort_keys=True).encode()
            ).hexdigest()[:16]
            self.controller.commit_checkpoint(self.step, digest)
        self.events.append(
            {"t": "checkpoint", "step": self.step, "seconds": time.perf_counter() - t0}
        )

    def restore_latest(self) -> bool:
        man = checkpoint.latest_manifest(self.ecfg.checkpoint_dir)
        if man is None:
            return False
        durable = self.controller.durable_step()
        if man["step"] > durable >= 0:
            # Never restore past the consensus-committed durability point.
            return False
        self.state = checkpoint.restore(self.ecfg.checkpoint_dir, man, self.state)
        self.state = jax.device_put(self.state, self._state_shardings)
        self.step = man["step"]
        self.events.append({"t": "restore", "step": self.step})
        return True
