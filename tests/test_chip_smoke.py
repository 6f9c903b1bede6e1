"""chip_smoke.py rehearsed on the CPU at the smoke config.

These check each phase's control flow and its own checks at a tiny size;
no number they see is a chip measurement.  The script itself refuses to
run anywhere but on a TPU, which the last two tests hold it to.
"""

import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.configs import get_smoke_config

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def cfg():
    return get_smoke_config("zamba2_1p2b")


def test_model_config_keeps_published_widths():
    cfg = chip_smoke.model_config()
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.head_dim) == (12, 2048, 32, 64)
    assert (cfg.d_ff, cfg.vocab, cfg.ssm_state, cfg.ssm_head_dim) == (8192, 32000, 64, 64)
    assert cfg.n_layers % cfg.hybrid_period == 0 and cfg.dtype == "bfloat16"


def test_train_phase(cfg):
    r = chip_smoke.phase_train(cfg, seq=32, batch=4, steps=8, seed=0)
    assert len(r["losses"]) == 8 and len(r["step_s"]) == 7
    assert r["ledger_last_step"] == 8
    assert r["ledger_durable_step"] == r["restored_step"] == 8
    assert set(r["checkpoint_s"]) == {4, 8}


def test_serve_phase(cfg):
    r = chip_smoke.phase_serve(cfg, batch=2, prompt_len=32, gen=4, seed=0)
    assert r["new_tokens"] == 4
    assert r["pallas_prefill_rel_err"] <= chip_smoke.PREFILL_RTOL
    assert not r["pallas_native"]  # the CPU runs the kernel in the interpreter


def test_ledger_phase():
    r = chip_smoke.phase_ledger(seed=0)
    assert r["commands_acked"] == 400 and r["slots_chosen"] >= 400
    assert r["violations"] == 0 and r["workers"] > 0


def test_elastic_phase_on_four_cpu_devices():
    script = textwrap.dedent(
        """
        import json, os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        sys.path.insert(0, os.getcwd())
        import chip_smoke
        from repro.configs import get_smoke_config
        cfg = get_smoke_config("zamba2_1p2b")
        r = chip_smoke.phase_elastic(cfg, seq=32, batch=4, stage_steps=3, seed=0)
        print(json.dumps(r))
        """
    )
    env = {**os.environ, "PYTHONPATH": str(ROOT / "src"), "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["meshes"] == [[4, 1], [2, 2], [2, 2]]
    assert r["final_pods"] == ["pod0", "pod4"]
    assert len(r["losses"]) == len(r["one_chip_losses"]) == 9
    assert r["max_rel_diff"] <= chip_smoke.LOSS_RTOL


def test_main_refuses_a_cpu_backend():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT,
    )
    assert out.returncode != 0
    assert "'cpu'" in out.stderr
    assert '"ok"' not in out.stdout


def test_fails_alone_without_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"],
        capture_output=True, text=True, timeout=300, env=env, cwd=tmp_path,
    )
    assert out.returncode != 0
    assert out.stdout == ""
