"""Serving launcher: batched prefill + decode with the Engine.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma2_2b --smoke \
      --batch 4 --prompt-len 16 --gen 24
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import get_model
from repro.serve import Engine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=24)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    if args.smoke:
        cfg = cfg.replace(dtype="float32")
    model = get_model(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    batch = {
        "tokens": jax.random.randint(key, (args.batch, args.prompt_len), 0, cfg.vocab)
    }
    if cfg.family == "encdec":
        batch["enc_emb"] = jax.random.normal(
            jax.random.fold_in(key, 1), (args.batch, cfg.enc_len, cfg.d_model)
        ).astype(jnp.dtype(cfg.dtype))

    eng = Engine(cfg, params, max_len=args.prompt_len + args.gen + 1)
    t0 = time.time()
    out = eng.generate(
        batch, args.gen, temperature=args.temperature,
        key=jax.random.PRNGKey(1) if args.temperature > 0 else None,
    )
    dt = time.time() - t0
    print(f"arch={cfg.arch_id} batch={args.batch} prompt={args.prompt_len} "
          f"generated={out.steps} tokens/request")
    dev = jax.devices()[0]
    print(f"wall {dt:.2f}s -> {args.batch * out.steps / dt:.1f} tok/s "
          f"({dev.platform} {dev.device_kind} x{jax.device_count()}, incl. compile)")
    for i in range(min(args.batch, 2)):
        print(f"  request {i}: {out.tokens[i].tolist()}")


if __name__ == "__main__":
    main()
