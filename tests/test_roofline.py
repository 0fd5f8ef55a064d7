"""Roofline machinery: HLO parsing, terms, cost-analysis semantics and the
unrolled-calibration identity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline.analysis import (
    HW, collective_bytes, dominant_term, parse_shape_bytes, roofline_terms,
)
from repro.roofline.calibrate import calibrated_costs
from repro.roofline.model_flops import model_flops, param_counts


class TestParsing:
    def test_shape_bytes(self):
        assert parse_shape_bytes("bf16[16,1184]{1,0}") == 16 * 1184 * 2
        assert parse_shape_bytes("f32[8]") == 32
        assert parse_shape_bytes("(f32[2,2], bf16[4])") == 16 + 8
        assert parse_shape_bytes("pred[10]") == 10
        assert parse_shape_bytes("f32[]") == 4

    def test_collective_bytes_synthetic(self):
        hlo = """
  %ar = f32[1024]{0} all-reduce(f32[1024]{0} %x), replica_groups=[16,32]<=[512]
  %ag = bf16[64,128]{1,0} all-gather(bf16[8,128]{1,0} %y), replica_groups=[64,8]<=[512], dimensions={0}
  %cp = f32[256]{0} collective-permute(f32[256]{0} %z), source_target_pairs={{0,1}}
  %rs = f32[16]{0} reduce-scatter(f32[128]{0} %w), replica_groups=[64,8]<=[512]
  %nc = f32[4096]{0} add(f32[4096]{0} %a, f32[4096]{0} %b)
"""
        st = collective_bytes(hlo)
        assert st.count == {"all-reduce": 1, "all-gather": 1,
                            "collective-permute": 1, "reduce-scatter": 1}
        assert st.per_op["all-reduce"] == 4096
        assert st.per_op["all-gather"] == 64 * 128 * 2 // 8  # operand = result/8
        assert st.per_op["collective-permute"] == 1024
        assert st.per_op["reduce-scatter"] == 16 * 4 * 8     # operand = result*8
        assert st.total == sum(st.per_op.values())

    def test_async_start_done_counted_once(self):
        hlo = """
  %s = f32[64]{0} all-reduce-start(f32[64]{0} %x), replica_groups={{0,1}}
  %d = f32[64]{0} all-reduce-done(f32[64]{0} %s)
"""
        st = collective_bytes(hlo)
        assert st.count.get("all-reduce", 0) == 1

    def test_terms_and_dominance(self):
        t = roofline_terms(197e12 * 256, 819e9 * 256, 0.0, 256)
        assert t["compute_s"] == pytest.approx(1.0)
        assert t["memory_s"] == pytest.approx(1.0)
        assert dominant_term({"compute_s": 3, "memory_s": 1,
                              "collective_s": 2}) == "compute"


class TestCostAnalysisSemantics:
    """Pin the XLA behaviors the methodology rests on."""

    def test_matmul_flops_exact(self):
        m = n = k = 256
        c = jax.jit(lambda a, b: a @ b).lower(
            jax.ShapeDtypeStruct((m, k), jnp.float32),
            jax.ShapeDtypeStruct((k, n), jnp.float32)).compile()
        assert c.cost_analysis()["flops"] == 2 * m * n * k

    def test_scan_body_counted_once(self):
        def scanned(a, bs):
            def body(c, b):
                return c @ b, None
            c, _ = jax.lax.scan(body, a, bs)
            return c

        a = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        f1 = jax.jit(scanned).lower(
            a, jax.ShapeDtypeStruct((1, 64, 64), jnp.float32)
        ).compile().cost_analysis()["flops"]
        f8 = jax.jit(scanned).lower(
            a, jax.ShapeDtypeStruct((8, 64, 64), jnp.float32)
        ).compile().cost_analysis()["flops"]
        # THE quirk calibration exists for: the matmul body is counted once
        # regardless of trip count (tiny loop-bookkeeping flops aside)
        assert abs(f8 - f1) < 100
        assert f1 >= 2 * 64 * 64 * 64  # exactly one body

    def test_unrolled_calibration_identity(self):
        """Extrapolation from unrolled G in {1,2} must reproduce the flops
        of a fully-unrolled G=5 program."""
        d = 64

        def make(g):
            def fn(x, ws):
                for i in range(g):
                    x = jnp.tanh(x @ ws[i])
                return x.sum()
            return jax.jit(fn).lower(
                jax.ShapeDtypeStruct((32, d), jnp.float32),
                jax.ShapeDtypeStruct((g, d, d), jnp.float32),
            ).compile()

        costs = calibrated_costs(lambda g: make(g), 5, scanned=True)
        truth = make(5).cost_analysis()["flops"]
        assert costs.flops_per_device == pytest.approx(truth, rel=1e-6)


class TestModelFlops:
    @pytest.mark.parametrize("arch", ["qwen2_7b", "gemma2_27b", "olmoe_1b_7b",
                                      "mamba2_370m", "recurrentgemma_9b",
                                      "hubert_xlarge"])
    def test_param_counts_match_init(self, arch):
        """Analytic N == actual init leaf sums (tp=1, full configs via
        eval_shape — no allocation)."""
        from repro.configs.base import get_config
        from repro.models.model import LanguageModel

        cfg = get_config(arch)
        lm = LanguageModel(cfg, tp=1)
        shapes, _ = lm.abstract_init()
        total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
        pc = param_counts(cfg)
        assert total == pc["total"], f"{arch}: {total} vs {pc['total']}"

    def test_moe_active_less_than_total(self):
        from repro.configs.base import get_config

        pc = param_counts(get_config("olmoe_1b_7b"))
        assert pc["active_non_embedding"] < pc["non_embedding"]
        # OLMoE: ~1B active vs ~6.9B total non-embedding
        assert 0.8e9 < pc["active_non_embedding"] < 1.6e9
        assert 6.0e9 < pc["non_embedding"] < 7.5e9

    def test_known_param_totals(self):
        """Sanity vs published sizes (within padding slack)."""
        from repro.configs.base import get_config

        assert abs(param_counts(get_config("qwen2_7b"))["total"] / 7.6e9 - 1) < 0.1
        assert abs(param_counts(get_config("gemma2_27b"))["total"] / 27.2e9 - 1) < 0.1
        assert abs(param_counts(get_config("mamba2_370m"))["total"] / 3.7e8 - 1) < 0.15

    def test_model_flops_shapes(self):
        from repro.configs.base import get_config
        from repro.configs.shapes import SHAPES

        cfg = get_config("qwen2_7b")
        tr = model_flops(cfg, SHAPES["train_4k"])
        pf = model_flops(cfg, SHAPES["prefill_32k"])
        dc = model_flops(cfg, SHAPES["decode_32k"])
        assert tr["spec"] == pytest.approx(
            6 * param_counts(cfg)["active_non_embedding"] * 256 * 4096)
        assert pf["refined"] > pf["spec"]
        assert dc["tokens"] == 128.0
