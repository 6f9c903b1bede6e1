#!/usr/bin/env python3
"""Drive the system's main path once on TPU chips and check what comes out.

  python chip_smoke.py [--seed N]        # one chip: train, serve, ledger
  python chip_smoke.py --chips 4         # four chips: the elastic remesh only

The model is zamba2-1.2b (Mamba-2 layers and one shared attention+MLP
block) at its published widths, cut to 12 layers: two whole periods of
the shared block.  Weights and data are random, made from ``--seed``.

One chip runs three phases in this one process, which holds the chip:

  train   ElasticTrainer at sequence 4096 and global batch 4 for 8 steps.
          The Matchmaker MultiPaxos ledger commits a step record every 2
          steps and a checkpoint every 4; the last checkpoint is restored
          through the ledger's durability check.
  serve   Engine on the same config: 8 requests, 1024-token prompts, 32
          new tokens.  The prefill is compiled once with the default
          attention and once with the Pallas flash kernel, and their
          logits are compared.
  ledger  The consensus protocol alone, every node its own OS process
          (``ClusterSpec.deploy("proc")``): 2 clients x 200 commands on a
          replicated KV store, then the invariant suite.  The workers
          import no JAX, so they never reach for the chip this process
          holds.

``--chips 4`` trains on four pods of one chip each, scales to two pods of
two chips, replaces a failed pod, and compares every step's loss with a
one-chip trainer on the same seed and data.  It runs no other phase.

Each phase prints one JSON line.  The last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
any failure raises before it and the exit code is nonzero.  Without a TPU
the script exits nonzero at once, naming the platform JAX found.  The
compile cache is ``$JAX_COMPILATION_CACHE_DIR`` or ``.jax_cache/`` in the
checkout (src/repro/launch/compile_cache.py).
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.coord import ElasticConfig, ElasticTrainer  # noqa: E402
from repro.core import ClusterSpec, KVStoreSM  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import get_model  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.serve import Engine  # noqa: E402
from repro.serve.engine import make_prefill_step  # noqa: E402
from repro.train import OptConfig  # noqa: E402
from repro.train.data import DataConfig  # noqa: E402

SEQ, TRAIN_BATCH, TRAIN_STEPS = 4096, 4, 8
COMMIT_EVERY, CHECKPOINT_EVERY = 2, 4
SERVE_BATCH, PROMPT_LEN, GEN = 8, 1024, 32
STAGE_STEPS = 3  # --chips 4: steps per mesh

# Pallas vs default prefill logits, as ||a - b|| / ||b||.  Both paths run
# the model in bf16 (unit roundoff 2**-9 ~ 2e-3).  They differ in where
# attention rounds: the default path rounds the score matrix and the
# softmax weights to bf16, the kernel keeps both in f32.  Each rounding is
# ~2e-3 relative; over 2 attention invocations feeding 12 residual layers
# that stays well under 5e-2, while a wrong mask, scale or block index
# moves the logits by O(1).
PREFILL_RTOL = 5e-2
# --chips 4 vs one chip, per-step loss: |a - b| <= LOSS_RTOL * |b|.  The
# four-chip step sums bf16 gradients across devices in another order than
# the one-chip step; on a loss near ln(32000) ~ 10.4 a relative 1e-2 is
# ~0.1 nats, far above that reordering and far below the change a wrong
# batch slice or a lost update makes after a few Adam steps.
LOSS_RTOL = 1e-2

_compile = {"secs": 0.0, "count": 0, "cache_hits": 0}


def _on_duration(event: str, duration_secs: float, **_: Any) -> None:
    # Wraps the backend compile or the persistent-cache read that replaces it.
    if event == "/jax/core/compile/backend_compile_duration":
        _compile["secs"] += duration_secs
        _compile["count"] += 1


def _on_event(event: str, **_: Any) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _compile["cache_hits"] += 1


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(msg)


def _emit(phase: str, **fields: Any) -> None:
    print(json.dumps({"phase": phase, **fields}, default=float), flush=True)


def _device() -> Dict[str, Any]:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}


def _peak_bytes() -> Any:
    stats = jax.devices()[0].memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


def model_config() -> ModelConfig:
    return get_config("zamba2_1p2b").replace(n_layers=12)


def _opt_config() -> OptConfig:
    return OptConfig(lr=3e-4, warmup_steps=2, total_steps=100)


def _fingerprint(tree: Any) -> List[float]:
    return [float(jnp.sum(x.astype(jnp.float32))) for x in jax.tree.leaves(tree)]


# --------------------------------------------------------------------------
def phase_train(
    cfg: ModelConfig, *, seq: int, batch: int, steps: int, seed: int
) -> Dict[str, Any]:
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=seq, global_batch=batch, seed=seed)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt") as ckpt_dir:
        t0 = time.perf_counter()
        trainer = ElasticTrainer(
            cfg,
            _opt_config(),
            dcfg,
            pods=["pod0"],
            ecfg=ElasticConfig(
                checkpoint_dir=ckpt_dir,
                commit_every=COMMIT_EVERY,
                checkpoint_every=CHECKPOINT_EVERY,
            ),
            seed=seed,
        )
        # Set-up: the first step's trace and compile, and its loss sync.
        c0 = dict(_compile)
        trainer.run(1)
        jax.block_until_ready(trainer.state)
        setup_s = time.perf_counter() - t0
        compile_s = _compile["secs"] - c0["secs"]
        cache_hits = _compile["cache_hits"] - c0["cache_hits"]

        c1 = _compile["count"]
        step_s = []
        for _ in range(steps - 1):
            t = time.perf_counter()
            trainer.run(1)
            jax.block_until_ready(trainer.state)
            step_s.append(time.perf_counter() - t)
        compiles_in_window = _compile["count"] - c1
        ckpt_s = {e["step"]: e["seconds"] for e in trainer.events if e["t"] == "checkpoint"}
        # Step time with the checkpoint save (host I/O) taken out.
        step_only_s = [
            s - ckpt_s.get(i + 2, 0.0) for i, s in enumerate(step_s)
        ]
        peak = _peak_bytes()

        losses = list(trainer.losses)
        _check(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")
        _check(compiles_in_window == 0, f"{compiles_in_window} compiles in the step window")
        trainer.controller.check_safety()
        ledger = trainer.controller.ledger()
        last_step, durable = ledger.last_step, ledger.durable_step
        want = steps - steps % CHECKPOINT_EVERY
        _check(durable == want, f"durable_step {durable} after {steps} steps, want {want}")

        before = _fingerprint(trainer.state)
        t = time.perf_counter()
        restored = trainer.restore_latest()
        restore_s = time.perf_counter() - t
        _check(restored, "restore_latest() refused the durable checkpoint")
        restored_step = trainer.step
        _check(restored_step == durable, f"restored step {restored_step} != durable {durable}")
        _check(_fingerprint(trainer.state) == before, "restored state differs from the saved one")
        del trainer
    gc.collect()
    return dict(
        setup_s=setup_s,
        compile_s=compile_s,
        compile_cache_hits=cache_hits,
        step_s=step_s,
        step_s_without_checkpoint=step_only_s,
        checkpoint_s=ckpt_s,
        restore_s=restore_s,
        losses=losses,
        peak_bytes_in_use=peak,
        ledger_last_step=last_step,
        ledger_durable_step=durable,
        restored_step=restored_step,
    )


def phase_serve(
    cfg: ModelConfig, *, batch: int, prompt_len: int, gen: int, seed: int
) -> Dict[str, Any]:
    key = jax.random.PRNGKey(seed)
    params = get_model(cfg).init(key)
    tokens = jax.random.randint(jax.random.fold_in(key, 1), (batch, prompt_len), 0, cfg.vocab)
    req = {"tokens": tokens}

    eng = Engine(cfg, params, max_len=prompt_len + gen)
    eng.generate(req, 2)  # compile prefill and decode
    t = time.perf_counter()
    out = eng.generate(req, gen)
    gen_s = time.perf_counter() - t
    _check(out.tokens.shape == (batch, gen), f"generated shape {out.tokens.shape}")
    _check(bool(((out.tokens >= 0) & (out.tokens < cfg.vocab)).all()), "token out of vocab")

    def prefill(c: ModelConfig):
        compiled = jax.jit(make_prefill_step(c)).lower(params, req).compile()
        logits, _ = compiled(params, req)
        return np.asarray(logits, np.float32), compiled.as_text()

    ref, _ = prefill(cfg)
    got, text = prefill(cfg.replace(attn_impl="pallas"))
    _check(bool(np.isfinite(ref).all() and np.isfinite(got).all()), "non-finite prefill logits")
    rel = float(np.linalg.norm(got - ref) / np.linalg.norm(ref))
    _check(rel <= PREFILL_RTOL, f"pallas prefill logits off by {rel} > {PREFILL_RTOL}")
    return dict(
        tokens_per_s=batch * out.steps / gen_s,
        generate_s=gen_s,
        batch=batch,
        prompt_len=prompt_len,
        new_tokens=out.steps,
        pallas_prefill_rel_err=rel,
        pallas_prefill_rtol=PREFILL_RTOL,
        pallas_native="tpu_custom_call" in text,
    )


def _jax_free(pid: int) -> bool:
    with open(f"/proc/{pid}/maps") as f:
        maps = f.read()
    return "libtpu" not in maps and "jaxlib" not in maps


def phase_ledger(*, seed: int) -> Dict[str, Any]:
    spec = ClusterSpec(f=1, n_clients=2, sm_factory=KVStoreSM, client_max_commands=200)
    t0 = time.perf_counter()
    t, dep = spec.deploy("proc", seed=seed)
    try:
        for c in dep.clients:
            c.op_factory = lambda n: ("set", f"k{n % 16}", n)
            c.start()
        t.run(300.0, until=lambda: all(c.done for c in dep.clients))
        wall_s = time.perf_counter() - t0
        _check(all(c.done for c in dep.clients), "ledger clients did not finish")
        workers = [p.pid for p in dep.supervisor.procs.values() if p.poll() is None]
        _check(all(_jax_free(pid) for pid in workers), "a ledger worker loaded JAX")
        acked = sum(len(c.latencies) for c in dep.clients)
    finally:
        dep.shutdown()
    shadow, violations = dep.gather()
    shutil.rmtree(t.workdir, ignore_errors=True)
    _check(not violations, f"ledger violations: {violations}")
    _check(acked == 2 * 200, f"{acked} commands acknowledged of 400")
    return dict(
        commands_acked=acked,
        slots_chosen=len(shadow.oracle.chosen),
        wall_s=wall_s,
        workers=len(workers),
        violations=0,
    )


# --------------------------------------------------------------------------
def _span_all_chips(trainer: ElasticTrainer) -> None:
    mesh_devs = set(trainer.mesh.devices.flat)
    _check(mesh_devs == set(jax.devices()), f"mesh {trainer.mesh.devices.shape} leaves chips idle")
    for leaf in jax.tree.leaves(trainer.state):
        _check(leaf.sharding.device_set == mesh_devs, "a state leaf misses mesh devices")


def phase_elastic(
    cfg: ModelConfig, *, seq: int, batch: int, stage_steps: int, seed: int
) -> Dict[str, Any]:
    """Four pods of one chip -> two pods of two chips -> a pod replaced,
    against one chip taking the same steps on the same data."""
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=seq, global_batch=batch, seed=seed)
    n = 3 * stage_steps
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt") as ckpt_dir:
        ecfg = ElasticConfig(
            checkpoint_dir=ckpt_dir, commit_every=COMMIT_EVERY, checkpoint_every=10**9
        )
        ref = ElasticTrainer(
            cfg, _opt_config(), dcfg, pods=["pod0"], seed=seed,
            ecfg=dataclasses.replace(ecfg, devices_per_pod=1),
        )
        ref.run(n)
        ref_losses = list(ref.losses)
        del ref
        gc.collect()

        tr = ElasticTrainer(
            cfg, _opt_config(), dcfg, pods=[f"pod{i}" for i in range(4)], seed=seed, ecfg=ecfg
        )
        meshes = []
        _span_all_chips(tr)
        tr.run(stage_steps)
        meshes.append(tr.mesh.devices.shape)
        tr.scale_to(["pod0", "pod1"])
        tr.run(stage_steps)
        _span_all_chips(tr)
        meshes.append(tr.mesh.devices.shape)
        tr.fail_and_replace("pod1", "pod4")
        tr.run(stage_steps)
        _span_all_chips(tr)
        meshes.append(tr.mesh.devices.shape)
        tr.controller.check_safety()
        losses = list(tr.losses)
        pods = list(tr.pods)
        del tr
    gc.collect()
    _check(all(math.isfinite(x) for x in losses + ref_losses), "non-finite loss")
    rel = [abs(a - b) / abs(b) for a, b in zip(losses, ref_losses)]
    _check(len(losses) == n and max(rel) <= LOSS_RTOL, f"losses off by {max(rel)} > {LOSS_RTOL}")
    return dict(
        losses=losses,
        one_chip_losses=ref_losses,
        max_rel_diff=max(rel),
        loss_rtol=LOSS_RTOL,
        meshes=[list(s) for s in meshes],
        final_pods=pods,
    )


# --------------------------------------------------------------------------
def main(argv: Any = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    dev = _device()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform {dev['platform']!r}", file=sys.stderr)
        return 1
    _check(dev["count"] >= args.chips, f"--chips {args.chips} but {dev['count']} found")
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    cfg = model_config()

    if args.chips == 4:
        _emit("elastic", device=dev, **phase_elastic(
            cfg, seq=SEQ, batch=TRAIN_BATCH, stage_steps=STAGE_STEPS, seed=args.seed
        ))
    else:
        _emit("train", device=dev, **phase_train(
            cfg, seq=SEQ, batch=TRAIN_BATCH, steps=TRAIN_STEPS, seed=args.seed
        ))
        serve = phase_serve(cfg, batch=SERVE_BATCH, prompt_len=PROMPT_LEN, gen=GEN, seed=args.seed)
        _check(serve["pallas_native"], "the Pallas prefill did not compile to a TPU kernel")
        _emit("serve", device=dev, **serve)
        _emit("ledger", **phase_ledger(seed=args.seed))
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
