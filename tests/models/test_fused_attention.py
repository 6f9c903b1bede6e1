"""The fused causal attention (splash kernels, interpret mode on the CPU)
against the jnp paths, and the rule by which ``attention`` picks a path.

On a CPU ``attention(attn_impl="auto")`` never takes the fused path; the
tests that need it steer ``_attention_path`` to answer as on one TPU chip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_smoke_config
from repro.kernels import ops as kops
from repro.models import layers
from repro.models.config import ModelConfig
from repro.models.lm import LM

BF16 = dict(rtol=3e-2, atol=3e-2)


def mha_cfg(**kw):
    d = dict(
        arch_id="t", family="dense", n_layers=1, d_model=64, vocab=128,
        n_heads=2, n_kv_heads=2, head_dim=128, d_ff=128, dtype="bfloat16",
    )
    d.update(kw)
    return ModelConfig(**d)


@pytest.fixture
def as_on_one_tpu(monkeypatch):
    """``attention`` decides as on one TPU chip, with blocks of 128; the
    kernels still run in the interpreter (kernels/backend.py)."""
    decide = layers._attention_path
    monkeypatch.setattr(
        layers,
        "_attention_path",
        lambda *a, **kw: decide(*a, **{**kw, "platform": "tpu", "devices": 1}),
    )
    monkeypatch.setattr(kops, "SPLASH_BLOCKS", {k: 128 for k in kops.SPLASH_BLOCKS})


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _kernel_against_naive(S):
    B, H, hd = 2, 2, 128
    cfg = mha_cfg()
    ks = jax.random.split(jax.random.PRNGKey(S), 4)
    q, k, v, ct = (jax.random.normal(x, (B, S, H, hd)).astype(jnp.bfloat16) for x in ks)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * ct.astype(jnp.float32))

    fused = lambda q, k, v: kops.fused_causal_attention(q, k, v)  # noqa: E731
    naive = lambda q, k, v: layers.attention_naive(q, k, v, cfg=cfg)  # noqa: E731
    np.testing.assert_allclose(
        np.asarray(jax.jit(fused)(q, k, v), np.float32),
        np.asarray(jax.jit(naive)(q, k, v), np.float32),
        **BF16,
    )
    got = jax.jit(jax.grad(loss(fused), argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(loss(naive), argnums=(0, 1, 2)))(q, k, v)
    for g, w in zip(got, want):
        assert _rel(g, w) < 1e-2
        np.testing.assert_allclose(np.asarray(g, np.float32), np.asarray(w, np.float32), rtol=5e-2, atol=6e-2)


def _shared_block_against_chunked(S):
    """The zamba2 shared block as ``_ssm_stack`` applies it: under
    ``jax.checkpoint``, inside ``lax.cond``; loss and every gradient."""
    cfg = get_smoke_config("zamba2_1p2b").replace(
        n_layers=2, hybrid_period=2, n_heads=2, n_kv_heads=2, head_dim=128, dtype="bfloat16"
    )
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, S), 0, cfg.vocab)
    ct = jax.random.normal(jax.random.PRNGKey(2), (2, S, cfg.d_model))

    def grads(c):
        lm = LM(c)
        params = lm.init(jax.random.PRNGKey(0))

        def loss(p):
            h, _ = lm.hidden_states(p, tokens, remat=True)
            return jnp.mean(h.astype(jnp.float32) * ct)

        return jax.jit(jax.value_and_grad(loss))(params)

    t = obs.Tracer(annotate=False)
    with t.step(0):
        lf, gf = grads(cfg)
    assert t.counters[0]["attention.fused"] == 1
    lc, gc = grads(cfg.replace(attn_impl="chunked", attn_q_chunk=64))
    # bf16 rounding: the chunked and naive paths differ by up to 2% a leaf
    assert abs(float(lf) - float(lc)) <= 3e-2 * abs(float(lc))
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(gf), jax.tree.leaves(gc)):
        assert _rel(g, w) < 5e-2, jax.tree_util.keystr(path)


@pytest.mark.parametrize(
    "what,S", [("kernel", 256), ("kernel", 512), ("shared_block", 256)]
)
def test_fused_matches_jnp_attention(as_on_one_tpu, what, S):
    if what == "kernel":
        _kernel_against_naive(S)
    else:
        _shared_block_against_chunked(S)


FUSED_OK = dict(
    q=(4, 4096, 32, 128), k=(4, 4096, 32, 128), cfg={}, causal=True, is_local=False,
    q_offset=0, platform="tpu", devices=1,
)


@pytest.mark.parametrize(
    "change,path",
    [
        ({}, "fused"),
        ({"is_local": None}, "fused"),  # no window: a traced flag is moot
        ({"q": (2, 512, 2, 128), "k": (2, 512, 2, 128)}, "fused"),
        ({"q": (1, 256, 2, 128), "k": (1, 256, 2, 128)}, "fused"),  # block = S
        ({"platform": "cpu"}, "chunked"),
        ({"platform": "gpu"}, "chunked"),
        ({"devices": 4}, "chunked"),
        ({"causal": False}, "chunked"),
        ({"cfg": {"sliding_window": 1024}, "is_local": True}, "chunked"),
        ({"cfg": {"sliding_window": 1024}, "is_local": None}, "chunked"),
        ({"cfg": {"sliding_window": 1024}, "is_local": False}, "fused"),
        ({"cfg": {"attn_logit_softcap": 50.0}}, "chunked"),
        ({"k": (4, 4096, 8, 128)}, "chunked"),  # GQA
        ({"q": (4, 4160, 32, 128), "k": (4, 4160, 32, 128)}, "chunked"),  # 4160 % 512
        ({"q": (4, 4096, 32, 64), "k": (4, 4096, 32, 64)}, "chunked"),  # head_dim 64
        ({"k": (4, 2048, 32, 128)}, "chunked"),  # Sq != Sk
        ({"q_offset": 256}, "chunked"),
        ({"cfg": {"sharding_policy": "tp"}}, "chunked"),
        ({"cfg": {"sharding_policy": "fsdp"}}, "fsdp"),
        ({"q": (4, 256, 32, 128), "k": (4, 256, 32, 128), "platform": "cpu"}, "naive"),
        ({"cfg": {"attn_impl": "chunked"}}, "chunked"),
        ({"cfg": {"attn_impl": "naive"}}, "naive"),
        ({"cfg": {"attn_impl": "pallas"}}, "pallas"),
    ],
)
def test_auto_takes_the_fused_path_only_where_it_applies(change, path):
    a = {**FUSED_OK, **change}
    cfg = mha_cfg(n_heads=32, n_kv_heads=32, **a.pop("cfg"))
    q, k = a.pop("q"), a.pop("k")
    assert layers._attention_path(q, k, cfg, **a) == path


@pytest.mark.parametrize("on_tpu,counted", [(False, "attention.chunked"), (True, "attention.fused")])
def test_a_traced_attention_call_counts_its_path(request, on_tpu, counted):
    if on_tpu:
        request.getfixturevalue("as_on_one_tpu")
    cfg = mha_cfg(attn_q_chunk=128)
    x = jax.ShapeDtypeStruct((1, 512, 2, 128), jnp.bfloat16)
    t = obs.Tracer(annotate=False)
    with t.step(3):
        jax.jit(lambda q, k, v: layers.attention(q, k, v, cfg=cfg)).lower(x, x, x)
    jax.jit(lambda q, k, v: layers.attention(q, k, v, cfg=cfg) + 1).lower(x, x, x)  # no step
    assert set(t.counters) == {3}
    assert {n: c for n, c in t.counters[3].items() if n.startswith("attention.")} == {counted: 1}
