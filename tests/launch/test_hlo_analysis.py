"""HLO analysis: loop-trip weighting, dot flops, collective parsing.

Includes the test that documents WHY this module exists:
``compiled.cost_analysis()`` counts while bodies once.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import hlo_analysis as ha
from repro.launch.roofline import collective_traffic, roofline_terms


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


class TestLoopWeighting:
    def test_cost_analysis_counts_loop_body_once(self):
        """The raw XLA cost analysis under-counts scans — the motivation
        for the structural analyzer."""

        def body(x, w):
            return jnp.tanh(x @ w), None

        W = jnp.zeros((8, 64, 64))
        x = jnp.zeros((4, 64))

        c = _compile(lambda x, W: jax.lax.scan(body, x, W)[0], x, W)
        ca = c.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        one_matmul = 2 * 4 * 64 * 64
        assert ca["flops"] < 2 * one_matmul  # counted once, not x8

    def test_analyzer_multiplies_by_trip_count(self):
        def body(x, w):
            return jnp.tanh(x @ w), None

        W = jnp.zeros((8, 64, 64))
        x = jnp.zeros((4, 64))
        c = _compile(lambda x, W: jax.lax.scan(body, x, W)[0], x, W)
        s = ha.analyze(c.as_text())
        one_matmul = 2 * 4 * 64 * 64
        assert s.flops == pytest.approx(8 * one_matmul, rel=0.01)

    def test_nested_scans_multiply(self):
        def inner(x, w):
            return x @ w, None

        def outer(x, W):
            def body(x, _):
                y, _ = jax.lax.scan(inner, x, W)
                return y, None

            return jax.lax.scan(body, x, None, length=5)[0]

        W = jnp.zeros((4, 32, 32))
        x = jnp.zeros((2, 32))
        c = _compile(outer, x, W)
        s = ha.analyze(c.as_text())
        one = 2 * 2 * 32 * 32
        assert s.flops == pytest.approx(5 * 4 * one, rel=0.01)

    def test_unrolled_matches_analyzer(self):
        def fn(x, W):
            for i in range(4):
                x = x @ W[i]
            return x

        W = jnp.zeros((4, 64, 64))
        x = jnp.zeros((4, 64))
        c = _compile(fn, x, W)
        s = ha.analyze(c.as_text())
        assert s.flops == pytest.approx(4 * 2 * 4 * 64 * 64, rel=0.01)


class TestScanSliceAccounting:
    def test_scan_weight_reads_are_slice_sized(self):
        """Stacked weights sliced per iteration must be charged L x slice
        bytes, not L x full-stack bytes (the L^2 trap)."""

        def body(x, w):
            return jnp.tanh(x @ w), None

        L, D = 16, 128
        W = jnp.zeros((L, D, D))
        x = jnp.zeros((2, D))
        c = _compile(lambda x, W: jax.lax.scan(body, x, W)[0], x, W)
        s = ha.analyze(c.as_text())
        full_stack = L * D * D * 4
        # Traffic must be far below L * full_stack (the naive accounting
        # would charge 16x full stack; fwd+bwd slice reads land ~3x).
        assert s.traffic_bytes < 6 * full_stack
        assert s.traffic_bytes > L * D * D * 4 * 0.5  # but sees the slices


class TestShapeParsing:
    def test_shape_bytes(self):
        assert ha._shape_bytes("f32[4,8]{1,0}") == 128
        assert ha._shape_bytes("bf16[10]") == 20
        assert ha._shape_bytes("(f32[2,2], s8[4])") == 20
        assert ha._shape_bytes("pred[]") == 1  # scalar pred: one byte

    def test_bf16_target_correction(self):
        assert ha._shape_bytes("f32[100]", f32_as=2) == 200
        assert ha._shape_bytes("bf16[100]", f32_as=2) == 200
        assert ha._shape_bytes("s32[100]", f32_as=2) == 400


class TestCollectives:
    def test_ring_traffic_formulas(self):
        colls = [
            {"op": "all-reduce", "result_bytes": 1024, "group_size": 4, "count": 2.0,
             "explicit_groups": None},
            {"op": "all-gather", "result_bytes": 4096, "group_size": 8, "count": 1.0,
             "explicit_groups": None},
        ]
        t = collective_traffic(colls, n_devices=8)
        want_ar = 2 * 1024 * 3 / 4 * 2.0
        want_ag = 4096 * 7 / 8
        assert t["ici"] == pytest.approx(want_ar + want_ag)
        assert t["by_op"]["all-reduce"] == pytest.approx(want_ar)

    def test_dcn_attribution(self):
        colls = [
            {"op": "all-reduce", "result_bytes": 100, "group_size": 2, "count": 1.0,
             "explicit_groups": [[0, 256]]},  # spans pods (pod_size=256)
            {"op": "all-reduce", "result_bytes": 100, "group_size": 2, "count": 1.0,
             "explicit_groups": [[0, 1]]},  # same pod
        ]
        t = collective_traffic(colls, n_devices=512, pod_size=256)
        assert t["dcn"] > 0 and t["ici"] > 0
        assert t["dcn"] == t["ici"]

    def test_roofline_terms_dominance(self):
        r = roofline_terms(
            flops_per_device=197e12,  # exactly 1s of compute
            bytes_per_device=819e9 / 2,  # 0.5s memory
            traffic={"ici": 0, "dcn": 0, "by_op": {}, "n": 0},
            device_kind="TPU v5 lite",
        )
        assert r["dominant"] == "compute_s"
        assert r["roofline_fraction"] == pytest.approx(1.0)
        r2 = roofline_terms(
            flops_per_device=197e12 / 10,
            bytes_per_device=819e9,
            traffic={"ici": 0, "dcn": 0, "by_op": {}, "n": 0},
            device_kind="TPU v5 lite",
        )
        assert r2["dominant"] == "memory_s"
        assert r2["roofline_fraction"] == pytest.approx(0.1)

    def test_roofline_terms_unknown_device_kind(self):
        with pytest.raises(ValueError, match="no published peaks"):
            roofline_terms(
                flops_per_device=1.0,
                bytes_per_device=1.0,
                traffic={"ici": 0, "dcn": 0, "by_op": {}, "n": 0},
                device_kind="cpu",
            )
