"""bench/layers.py with the program's own tracer attached: CPU rehearsals
of a tiny cell carry its spans and counters, and a long step carries what
the tracer recorded of the host over it."""

import os
import subprocess
import sys
import time

from bench import layers
from bench.harness import LONG_STEP, ROOT
from bench.tests.tiny import make_root

SEED = 2**31 + 991
SPANS = {"step", "batch", "dispatch", "loss_sync", "ledger.advance", "ledger.commit"}


def test_untraced_long_step_carries_host_usage():
    from repro.obs import Tracer

    t = Tracer(annotate=False)
    t0 = time.perf_counter()
    for k in range(7):
        with t.step(k), t.span("batch"):
            # step 3 sleeps far past LONG_STEP x a normal step, even on a
            # loaded host; the others barely run
            time.sleep(0.3 if k == 3 else 0.002)
    long = layers.long_steps(t, t0, time.perf_counter())[0]  # the longest
    assert long["step"] == 3 and long["seconds"] > LONG_STEP * 0.002
    assert long["spans"] == {"batch": long["spans"]["batch"]} and long["spans"]["batch"] >= 0.3
    assert long["invol_switches"] >= 0
    assert 0 <= long["cpu_s"] < long["seconds"]  # the host slept, it did not run
    assert "device" not in long
    dev = [{"busy_s": 0.001, "longest_op": ["fusion.1", 0.001]}] * 7
    assert layers.long_steps(t, t0, time.perf_counter(), dev)[0]["device"] == dev[3]


def test_untraced_rehearsal_carries_program_spans(tmp_path):
    out = layers.run("tiny-train", SEED, 0.3, False, root=make_root(tmp_path), require_chip=False)
    prog = out["program"]
    assert SPANS <= set(prog["spans"])
    assert prog["steps"] == out["steps"] == prog["spans"]["step"]["count"]
    for s in prog["spans"].values():
        assert 0 <= s["self_s"] <= s["total_s"]
    ticks = prog["counters"]["ledger.ticks"]
    assert ticks["total"] >= prog["spans"]["ledger.commit"]["count"] >= 1
    assert "compiles" not in prog["counters"]
    assert out["train_tokens_per_s"] > 0 and isinstance(out["long_steps"], list)
    assert "scopes" not in out


def test_traced_run_carries_program_spans(tmp_path):
    out = layers.run("tiny-train", SEED, 0.3, True, root=make_root(tmp_path), require_chip=False)
    assert SPANS <= set(out["program"]["spans"])
    assert "scopes" not in out  # a CPU's trace holds no TPU to put down to scopes


def test_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run(
        [sys.executable, "bench/layers.py", "--workload", "zamba2-train", "--seed", "5", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 3 and r.stdout.strip() == ""
    assert "TPU" in r.stderr


def test_op_names_come_from_this_program_not_the_cache(tmp_path):
    """The persistent cache's key leaves out metadata: a step whose scopes
    changed loads the old executable, with the old op_names."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from bench.harness import enable_cache

    scope = ["before"]

    def step(state, batch):
        with jax.named_scope(scope[0]):
            return jnp.sin(state) * batch

    class Trainer:
        state = jnp.ones(8)

        def _step_jit(self):
            return jax.jit(step)

        def _batch(self):
            return jnp.full(8, 2.0)

    keys = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes", "jax_enable_compilation_cache")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        enable_cache(jax, tmp_path)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
        jax.jit(step)(Trainer.state, Trainer()._batch()).block_until_ready()
        jax.clear_caches()
        scope[0] = "after"
        jax.jit(step)(Trainer.state, Trainer()._batch()).block_until_ready()  # served by the cache
        paths = layers.step_op_names(Trainer()).values()
        assert any("/after/" in p for p in paths) and not any("/before/" in p for p in paths)
        assert jax.config.jax_enable_compilation_cache
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
