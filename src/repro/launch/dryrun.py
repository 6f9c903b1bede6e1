import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

# ^ MUST precede every other import (jax locks the device count and the
# platform on first init): the 512 fake devices are host CPUs, and a
# machine with a TPU must not hand its chip to the dry run instead.
# Everything below is ordinary code.

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell against the production mesh, prove it fits
(memory_analysis), extract FLOPs/bytes (cost_analysis) and the collective
schedule (HLO parse), and write a JSON artifact for EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch grok_1_314b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --list
"""

import argparse
import json
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, get_config, normalize, shape_applicable
from repro.coord.elastic import state_specs
from repro.launch import hlo_analysis, roofline as rl
from repro.launch.mesh import PRODUCTION_DEVICE_KIND, make_production_mesh, peaks_for
from repro.models import get_model
from repro.models.config import ModelConfig
from repro.models.sharding import (
    axis_sizes,
    batch_spec,
    decode_state_specs,
    named,
    param_specs,
    policy_for,
)
from repro.serve.engine import make_decode_step, make_prefill_step
from repro.train import OptConfig, init_state, make_train_step


# --------------------------------------------------------------------------
# Production config overrides (documented in DESIGN.md Section 4)
# --------------------------------------------------------------------------
def production_config(arch: str, shape: str) -> ModelConfig:
    from repro.models.sharding import policy_for

    cfg = get_config(arch)
    kind = SHAPES[shape][2]
    policy = policy_for(cfg, kind)
    over: Dict[str, Any] = dict(
        dtype="bfloat16",
        sharding_policy=policy,
        attn_impl="chunked",  # jnp statement of the flash-attention blocking
        attn_q_chunk=256,
        moe_group_size=512,
    )
    if policy == "fsdp" and kind == "train":
        # Sequence is sharded over 'model' and the vocab over the flat
        # FSDP axis -> per-device logits are tiny; no loss chunking.
        # Attention runs under shard_map on local shapes with a small
        # q-chunk (the (Cq, Sk) f32 logits block is the memory knob).
        over["loss_seq_chunks"] = 1
        over["attn_q_chunk"] = 64
    elif shape == "train_4k":
        over["loss_seq_chunks"] = 16 if cfg.vocab >= 131072 else 8
    return cfg.replace(**over)


def opt_config(cfg: ModelConfig) -> OptConfig:
    # int8 second moments for the XXL MoE configs: fp32 m+v for 314B params
    # does not fit 256 chips; blockwise-8-bit does (EXPERIMENTS.md Dry-run).
    big = cfg.param_count() > 60e9
    return OptConfig(int8_state=big)


def sds(shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def shard_like(mesh, tree_shapes, tree_specs):
    return jax.tree.map(
        lambda t, s: sds(t.shape, t.dtype, NamedSharding(mesh, s)),
        tree_shapes,
        tree_specs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct) or isinstance(x, P),
    )


# --------------------------------------------------------------------------
# Cell builders: (callable, args-as-ShapeDtypeStructs, out_shardings)
# --------------------------------------------------------------------------
def build_cell(arch: str, shape: str, mesh) -> Tuple[Any, tuple, Any, Dict[str, Any]]:
    cfg = production_config(arch, shape)
    seq, batch, kind = SHAPES[shape]
    maxes = axis_sizes(mesh)
    model = get_model(cfg)
    policy = policy_for(cfg, kind)
    info: Dict[str, Any] = {"kind": kind, "seq": seq, "batch": batch, "policy": policy}

    tok_sh = NamedSharding(mesh, batch_spec(cfg, (batch, seq), maxes, policy))

    if kind == "train":
        ocfg = opt_config(cfg)
        state_shapes = jax.eval_shape(
            lambda: init_state(cfg, ocfg, jax.random.PRNGKey(0))
        )
        specs = state_specs(cfg, state_shapes, maxes, policy=policy)
        state_in = shard_like(mesh, state_shapes, specs)
        batch_in = {
            "tokens": sds((batch, seq), jnp.int32, tok_sh),
            "targets": sds((batch, seq), jnp.int32, tok_sh),
        }
        if cfg.family == "encdec":
            emb_sh = NamedSharding(
                mesh, batch_spec(cfg, (batch, seq, cfg.d_model), maxes, policy)
            )
            batch_in["enc_emb"] = sds((batch, seq, cfg.d_model), jnp.bfloat16, emb_sh)
        n_micro = 1
        if cfg.param_count() > 60e9:
            n_micro = 16  # XXL MoE: bound dispatch/dW activation memory
        elif cfg.param_count() > 25e9:
            n_micro = 4
        elif cfg.vocab >= 200_000:
            n_micro = 2  # giant-vocab dense: bound logits/embed-grad memory
        pspec_tree = param_specs(cfg, state_shapes.params, maxes, policy=policy)
        fn = make_train_step(
            cfg, ocfg, microbatches=n_micro, grad_specs=pspec_tree
        )
        info["microbatches"] = n_micro
        out_shardings = (named(mesh, specs), None)
        info["tokens"] = batch * seq
        info["model_flops"] = 6 * cfg.param_count(active_only=True) * batch * seq
        return fn, (state_in, batch_in), out_shardings, info

    # -- serving paths: params in bf16, no optimizer --------------------------
    param_shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    pspecs = param_specs(cfg, param_shapes, maxes, policy="tp")
    params_in = shard_like(mesh, param_shapes, pspecs)

    if kind == "prefill":
        batch_in = {"tokens": sds((batch, seq), jnp.int32, tok_sh)}
        if cfg.family == "encdec":
            emb_sh = NamedSharding(
                mesh, batch_spec(cfg, (batch, cfg.enc_len, cfg.d_model), maxes, policy)
            )
            batch_in["enc_emb"] = sds(
                (batch, cfg.enc_len, cfg.d_model), jnp.bfloat16, emb_sh
            )
        fn = make_prefill_step(cfg)
        out_shapes = jax.eval_shape(fn, param_shapes, batch_in)
        sspecs = decode_state_specs(cfg, out_shapes[1], maxes)
        out_shardings = (None, named(mesh, sspecs))
        info["tokens"] = batch * seq
        info["model_flops"] = 2 * cfg.param_count(active_only=True) * batch * seq
        return fn, (params_in, batch_in), out_shardings, info

    # kind == "decode": one new token against a seq-long cache
    if cfg.family == "encdec":
        mem_shape = sds((batch, cfg.enc_len, cfg.d_model), jnp.bfloat16)
        state_shapes = jax.eval_shape(
            lambda p, m: model.decode_init(p, batch, seq, m), param_shapes, mem_shape
        )
    else:
        state_shapes = jax.eval_shape(lambda: model.decode_init(batch, seq))
    sspecs = decode_state_specs(cfg, state_shapes, maxes)
    state_in = shard_like(mesh, state_shapes, sspecs)
    tokens_in = sds(
        (batch, 1), jnp.int32, NamedSharding(mesh, batch_spec(cfg, (batch, 1), maxes, "tp"))
    )
    fn = make_decode_step(cfg)
    out_shardings = (None, named(mesh, sspecs))
    info["tokens"] = batch
    info["model_flops"] = 2 * cfg.param_count(active_only=True) * batch
    return fn, (params_in, state_in, tokens_in), out_shardings, info


# --------------------------------------------------------------------------
def run_cell(
    arch: str, shape: str, *, multi_pod: bool, out_dir: Optional[str] = None
) -> Dict[str, Any]:
    cfg = get_config(arch)
    ok, reason = shape_applicable(cfg, shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    art: Dict[str, Any] = {
        "arch": arch,
        "shape": shape,
        "mesh": mesh_name,
        "params": cfg.param_count(),
        "active_params": cfg.param_count(active_only=True),
    }
    if not ok:
        art["skipped"] = reason
        _write(art, out_dir)
        print(f"SKIP {arch} {shape}: {reason}")
        return art

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    fn, args, out_shardings, info = build_cell(arch, shape, mesh)
    art.update(info)
    # Donate the mutable state buffers (train state / decode caches) — real
    # deployments alias them, and the memory analysis should reflect that.
    kind = info["kind"]
    donate = (0,) if kind == "train" else ((1,) if kind == "decode" else ())

    t0 = time.time()
    with jax.set_mesh(mesh):
        lowered = jax.jit(
            fn, out_shardings=out_shardings, donate_argnums=donate
        ).lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    print("memory_analysis:", mem)  # proves it fits
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    print(
        "cost_analysis (raw, loop bodies counted once): "
        "flops/device=%.3e bytes/device=%.3e"
        % (cost.get("flops", 0.0), cost.get("bytes accessed", 0.0))
    )

    hlo = compiled.as_text()
    summary = hlo_analysis.analyze(hlo, bf16_target=True)
    pod_size = 256 if multi_pod else None
    traffic = rl.collective_traffic(
        summary.collectives, n_devices=n_dev, pod_size=pod_size
    )
    roof = rl.roofline_terms(
        flops_per_device=summary.flops,
        bytes_per_device=summary.traffic_bytes,
        traffic=traffic,
        device_kind=PRODUCTION_DEVICE_KIND,
    )

    per_dev_bytes = {
        "argument": int(mem.argument_size_in_bytes),
        "output": int(mem.output_size_in_bytes),
        "temp": int(mem.temp_size_in_bytes),
        "alias": int(mem.alias_size_in_bytes),
        "peak_estimate": int(
            mem.argument_size_in_bytes
            + mem.output_size_in_bytes
            + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes  # donated buffers counted once
        ),
    }
    art.update(
        {
            "n_devices": n_dev,
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "flops_per_device": summary.flops,
            "bytes_per_device": summary.traffic_bytes,
            "raw_cost_analysis": {
                "flops": cost.get("flops", 0.0),
                "bytes_accessed": cost.get("bytes accessed", 0.0),
            },
            "memory": per_dev_bytes,
            "fits_hbm16g": per_dev_bytes["peak_estimate"]
            < peaks_for(PRODUCTION_DEVICE_KIND).hbm_bytes,
            "useful_flops_ratio": (
                art["model_flops"] / (summary.flops * n_dev)
                if summary.flops
                else 0.0
            ),
            "roofline": roof,
            "hlo_bytes": len(hlo),
        }
    )
    _write(art, out_dir)
    print(rl.summarize_artifact(art))
    print(
        f"peak/device = {per_dev_bytes['peak_estimate']/2**30:.2f} GiB "
        f"(fits 16G: {art['fits_hbm16g']}); compile {t_compile:.1f}s"
    )
    return art


def _write(art: Dict[str, Any], out_dir: Optional[str]) -> None:
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(
            out_dir, f"{normalize(art['arch'])}__{art['shape']}__{art['mesh']}.json"
        )
        with open(path, "w") as f:
            json.dump(art, f, indent=1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=False)
    ap.add_argument("--shape", required=False, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()
    if args.list:
        from repro.configs import ARCH_IDS, cells

        for a, s in cells():
            print(a, s)
        return
    run_cell(args.arch, args.shape, multi_pod=args.multi_pod, out_dir=args.out)


if __name__ == "__main__":
    main()
