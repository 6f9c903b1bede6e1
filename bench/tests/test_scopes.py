"""Layer scopes: the program's train step carries them in its op_name
metadata, and bench/layers.py puts device time and idle gaps down to them
and to the program's own spans."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import layers, trace
from bench.drivers.train import program_config
from bench.tests.tiny import MAMBA, OPT, ZAMBA

FIXTURE = Path(__file__).parent / "fixtures" / "trace_scoped.json"


@pytest.mark.parametrize(
    "config, names",
    [
        (ZAMBA, layers.SCOPES),
        (MAMBA, ("layers", "mamba", "ssd", "embed", "head", "optimizer")),
    ],
    ids=["zamba2", "mamba2"],
)
def test_tiny_train_step_carries_every_scope(config, names):
    from repro.train import OptConfig, init_state, make_train_step

    cfg, ocfg = program_config(config), OptConfig(**OPT)
    state = jax.eval_shape(lambda k: init_state(cfg, ocfg, k), jax.random.PRNGKey(0))
    batch = {k: jax.ShapeDtypeStruct((2, 64), jnp.int32) for k in ("tokens", "targets")}
    text = jax.jit(make_train_step(cfg, ocfg)).lower(state, batch).compile().as_text()
    paths = layers.op_names_from_hlo(text)
    found = {layers.scope_of(p) for p in paths.values()}
    assert set(names) <= found
    assert any("rematted_computation" in p for p in paths.values())
    assert any(p.startswith("jit(train_step)/transpose(jvp(layers))") for p in paths.values())


@pytest.mark.parametrize(
    "path, scope",
    [
        ("jit(train_step)/jvp(layers)/while/body/closed_call/mamba/ssd/exp", "ssd"),
        ("jit(train_step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/mamba/mul", "mamba"),
        ("jit(train_step)/transpose(jvp(head))/while/body/closed_call/bcd,dv->bcv/dot_general", "head"),
        ("jit(train_step)/jvp(layers)/while/body/cond/branch_1_fun/shared_block/mlp/dot_general", "mlp"),
        ("jit(train_step)/jvp(layers)/while/body/dynamic_update_slice", "layers"),
        ("jit(train_step)/optimizer/sqrt", "optimizer"),
        ("jit(train_step)/transpose(jvp(embed))/scatter-add", "embed"),
        ("jit(train_step)/jvp(headless)/add", "unscoped"),
        ("", "unscoped"),
    ],
)
def test_scope_of_takes_the_innermost_name(path, scope):
    assert layers.scope_of(path) == scope


def test_op_names_from_hlo_text():
    text = (
        "%body (p: f32[4]) -> f32[4] {\n"
        "  %copy.2 = f32[4]{0} copy(%p)\n"
        '  ROOT %mul.3 = f32[4]{0} multiply(%copy.2, %copy.2), metadata={op_name="jit(f)/layers/while/body/mul" stack_frame_id=3}\n'
        "}\n\n"
        "ENTRY %main (x: f32[4]) -> f32[4] {\n"
        '  %while.7 = f32[4]{0} while(%x), condition=%cond, body=%body, metadata={op_name="jit(f)/layers/while"}\n'
        "  ROOT %copy.1 = f32[4]{0} copy(%while.7)\n"
        "}\n"
    )
    # the compiler's copy in the loop body takes the loop's op_name; the
    # copy at the top level has none to take
    assert layers.op_names_from_hlo(text) == {
        "copy.2": "jit(f)/layers/while",
        "mul.3": "jit(f)/layers/while/body/mul",
        "while.7": "jit(f)/layers/while",
    }


@pytest.fixture(scope="module")
def fixture():
    return json.loads(FIXTURE.read_text())


@pytest.fixture(scope="module")
def reduced(fixture):
    return layers.reduce(fixture)


def test_scopes_split_train_step_device_time(reduced):
    s = reduced["scopes"]
    ms = lambda ns: pytest.approx(ns * 1e-6)  # noqa: E731  per run, both runs alike
    assert s["ssd"] == {"ms": ms(1500), "recompute_ms": ms(300), "xla_remat_ms": ms(400)}
    assert s["layers"]["ms"] == ms(500)  # the loop's own time, less its body
    assert s["attn"]["ms"] == ms(300) and s["shared_block"]["ms"] == ms(200)
    assert s["head"]["ms"] == ms(300) and s["optimizer"]["ms"] == ms(200) and s["embed"]["ms"] == ms(40)
    # a convert outside every scope, and an op with no op_name at all
    assert s["unscoped"]["ms"] == ms(150)
    # the batch's copy runs outside the train step and is in no scope
    assert sum(v["ms"] for v in s.values()) == ms(3190)
    assert reduced["coverage"] == {"scoped": pytest.approx(1 - 150 / 3190), "no_op_name": pytest.approx(50 / 3190)}


def test_scopes_go_unread_when_the_op_names_miss_the_step(fixture):
    t = dict(fixture, op_names={"fusion.15": fixture["op_names"]["fusion.15"]})
    r = layers.reduce(t)
    assert r["scopes"] is None
    assert r["coverage"] == {"scoped": pytest.approx(200 / 3190), "no_op_name": pytest.approx(1 - 200 / 3190)}


def test_idle_is_the_overlap_with_each_program_span(reduced):
    per_step = lambda ns: pytest.approx(ns * 1e-6 / 2)  # noqa: E731
    # batch: [0,500) and [700,1000) of step 0, [5000,6500) of step 1;
    # the gap [4390,6600) is split between loss_sync, ledger.advance,
    # the step alone, batch and dispatch
    assert reduced["idle_ms"] == {
        "all": per_step(3420),
        "step": per_step(3420),
        "batch": per_step(2300),
        "loss_sync": per_step(610),
        "dispatch": per_step(200),
        "ledger.advance": per_step(100),
        "ledger.commit": per_step(50),
    }


def test_input_idle_holds_when_a_gap_leans_to_loss_sync(fixture, reduced):
    """Without step 0's tail on the device, its gap runs from 3100 to 6600:
    1700 of it under loss_sync, 1500 under batch.  A rule that gave the
    whole gap to one span would move it all to loss_sync; the part under
    batch is the same as before, and so is the batch idle."""
    t = json.loads(json.dumps(fixture))
    dev = next(p for p in t["planes"] if p["name"] == "/device:TPU:0")
    ops = next(ln for ln in dev["lines"] if ln["name"] == "XLA Ops")
    ops["events"] = [e for e in ops["events"] if not 3200 <= e[1] < 4400]
    idle = layers.reduce(t)["idle_ms"]
    assert idle["loss_sync"] == pytest.approx((1700 + 100) * 1e-6 / 2)
    assert idle["batch"] == reduced["idle_ms"]["batch"]


def test_steps_give_device_busy_and_busiest_op(reduced):
    assert reduced["device_steps"] == [
        {"busy_s": pytest.approx(3390e-9), "longest_op": ["fusion.10", pytest.approx(800e-9)]},
        {"busy_s": pytest.approx(3190e-9), "longest_op": ["fusion.10", pytest.approx(800e-9)]},
    ]


def test_existing_numbers_ignore_the_program_spans(fixture):
    t = json.loads(json.dumps(fixture))
    for p in t["planes"]:
        for ln in p["lines"]:
            ln["events"] = [e for e in ln["events"] if not e[0].startswith("repro.") and e[0] != "train"]
    with_spans, bare = trace.reduce(fixture, n_chips=1), trace.reduce(t, n_chips=1)
    for k in ("window_s", "busy_s", "devices", "device_ops", "idle_gaps", "modules"):
        assert bare[k] == with_spans[k]
    assert layers.reduce(t)["idle_ms"] == {"all": pytest.approx(3420e-6)}


def test_a_trace_without_a_tpu_gives_nothing(fixture):
    t = dict(fixture, planes=[p for p in fixture["planes"] if p["name"].startswith("/host:")])
    assert layers.reduce(t) == {}
