"""The Pallas kernels compile natively for a TPU v5e at zamba2-1.2b widths.

Interpret mode (test_kernels.py) checks what the kernels compute; only the
chip's own compiler shows what it refuses (a primitive with no Mosaic
lowering, a block not aligned to the tiling, too much VMEM).  These tests
compile each kernel with ``interpret=False`` against a described v5e
topology (no chip needed) and check that the kernel is in the program.
"""

import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import decode_attention_bkh
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.ssd_scan import ssd_intra_chunk


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it.
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_topology_is_v5e(topo):
    assert topo.devices[0].device_kind == "TPU v5 lite"


def test_flash_attention_compiles(one_chip):
    B, H, S, hd = 2, 32, 4096, 64
    fn = partial(flash_attention_bhsd, scale=hd**-0.5, causal=True, interpret=False)
    qkv = ((B, H, S, hd), jnp.bfloat16)
    assert "tpu_custom_call" in _compile_text(fn, one_chip, qkv, qkv, qkv)


def test_decode_attention_compiles(one_chip):
    B, K, S, hd = 8, 32, 4096, 64
    fn = partial(decode_attention_bkh, scale=hd**-0.5, interpret=False)
    text = _compile_text(
        fn,
        one_chip,
        ((B, K, hd), jnp.bfloat16),
        ((B, K, S, hd), jnp.bfloat16),
        ((B, K, S, hd), jnp.bfloat16),
        ((B,), jnp.int32),
    )
    assert "tpu_custom_call" in text


def test_ssd_intra_chunk_compiles(one_chip):
    B, nh, nC, Q, hd, N = 2, 64, 16, 256, 64, 64  # sequence 4096 in 256-chunks
    fn = partial(ssd_intra_chunk, interpret=False)
    text = _compile_text(
        fn,
        one_chip,
        ((B, nh, nC, Q, hd), jnp.bfloat16),
        ((B, nh, nC, Q), jnp.float32),
        ((B, nh, nC, Q, N), jnp.bfloat16),
        ((B, nh, nC, Q, N), jnp.bfloat16),
    )
    assert "tpu_custom_call" in text
