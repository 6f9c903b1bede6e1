"""The program's tracer: the NULL tracer records nothing, a recording
tracer nests spans and assigns them to steps, compiles are counted under
the step that made them, and the trainer and its ledger report their
spans and counters through it."""

import jax
import jax.numpy as jnp
import pytest

from repro import obs


def test_null_tracer_records_nothing_and_shares_one_context():
    t = obs.NULL
    ctx = t.span("batch")
    assert ctx is t.span("dispatch") is t.step(3)
    with t.step(3) as a, t.span("batch") as b:
        t.count("ledger.ticks", 5)
    assert a is b is ctx
    assert not hasattr(t, "records") and not hasattr(t, "counters")


@pytest.mark.parametrize("annotate", [False, True])
def test_recording_tracer_nests_parents_and_assigns_steps(annotate):
    t = obs.Tracer(annotate=annotate)
    with t.span("setup"):
        t.count("ledger.ticks", 2)
    for step in (4, 5):
        with t.step(step):
            with t.span("batch"):
                pass
            with t.span("ledger.commit"):
                t.count("ledger.ticks", 3)
                with t.span("inner"):
                    pass
    names = [r[0] for r in t.records]
    assert names == ["setup"] + ["batch", "inner", "ledger.commit", "step"] * 2
    assert [r[3] for r in t.records[1:5]] == [1, 2, 1, 0]
    assert all(t0 <= t1 for _, t0, t1, _ in t.records)
    assert t.parents[:5] == [None, "step", "ledger.commit", "step", None]
    assert t.steps_of == [None] + [4] * 4 + [5] * 4
    assert t.counters == {None: {"ledger.ticks": 2}, 4: {"ledger.ticks": 3}, 5: {"ledger.ticks": 3}}
    assert set(t.step_attrs) == {4, 5}
    assert set(t.step_attrs[4]) == {"cpu_s", "invol_switches"}
    assert t.step_attrs[4]["cpu_s"] >= 0 and t.step_attrs[4]["invol_switches"] >= 0
    assert len(t.durations("batch")) == 2


def test_a_span_that_raises_is_recorded_and_closed():
    t = obs.Tracer(annotate=False)
    with pytest.raises(ValueError):
        with t.step(0), t.span("checkpoint"):
            raise ValueError("disk full")
    assert [r[0] for r in t.records] == ["checkpoint", "step"]
    assert t._open_names == [] and t.steps_of == [0, 0]
    with t.span("after"):
        pass
    assert t.steps_of[-1] is None and t.records[-1][3] == 0


def test_a_recompile_inside_a_step_counts_under_that_step():
    t = obs.Tracer(annotate=False)
    f = jax.jit(lambda x: x * 2 + 1)
    f(jnp.ones(3)).block_until_ready()
    with t.step(0):
        f(jnp.ones(3)).block_until_ready()
    with t.step(1):
        f(jnp.ones(7)).block_until_ready()  # a new shape compiles
    f(jnp.ones(11)).block_until_ready()  # outside any step: nobody's
    assert "compiles" not in t.counters.get(0, {})
    assert t.counters[1]["compiles"] >= 1
    assert None not in t.counters


def test_trainer_and_ledger_spans(tmp_path):
    from repro.configs import get_smoke_config
    from repro.coord import ElasticConfig, ElasticTrainer
    from repro.train import OptConfig
    from repro.train.data import DataConfig

    cfg = get_smoke_config("stablelm_12b").replace(dtype="float32")
    tr = ElasticTrainer(
        cfg,
        OptConfig(lr=3e-3, warmup_steps=5, total_steps=200),
        DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=4, seed=0),
        pods=["pod0"],
        ecfg=ElasticConfig(checkpoint_dir=str(tmp_path), checkpoint_every=4, commit_every=2),
    )
    assert tr.tracer is obs.NULL and tr.controller.tracer is obs.NULL
    tr.run(1)
    t = obs.Tracer(annotate=False)
    tr.tracer = t
    assert tr.controller.tracer is t
    tr.run(4)  # steps 1..4 (0-based): commits after 2 and 4, a checkpoint after 4
    tel = tr.scale_to(["pod0", "pod1"])
    tr.run(1)

    by = lambda n: [k for (name, *_), k in zip(t.records, t.steps_of) if name == n]  # noqa: E731
    assert by("step") == [1, 2, 3, 4, 5]
    for name in ("batch", "dispatch", "loss_sync", "ledger.advance"):
        assert by(name) == [1, 2, 3, 4, 5]
    # StepRecords after steps 2 and 4, the checkpoint's commit, the
    # reconfiguration's entry (outside any step), the StepRecord after 6
    assert by("ledger.commit") == [1, 3, 3, None, 5]
    assert by("checkpoint") == [3]
    assert by("ledger.reconfigure") == [None]
    assert by("remesh") == [5]  # the ledger's new epoch reaches the trainer
    parent = dict(zip([r[0] for r in t.records], t.parents))
    assert parent["checkpoint"] == "step" and parent["batch"] == "step"
    assert t.parents[[r[0] for r in t.records].index("ledger.commit")] == "step"
    assert t.counters[1]["ledger.ticks"] >= 1 and t.counters[3]["ledger.ticks"] >= 2
    assert set(t.step_attrs) == {1, 2, 3, 4, 5}
    ckpt = next(e for e in tr.events if e["t"] == "checkpoint")
    # each is timed around its span
    assert 0 <= ckpt["seconds"] - t.durations("checkpoint")[0] < 1e-3
    assert 0 <= tel["wall_ms"] - 1e3 * t.durations("ledger.reconfigure")[0] < 1.0
    assert tel["activation_ms"] < 5.0
