"""Roofline machinery: collective parser against hand-built HLO snippets,
cost-calibration arithmetic, and an end-to-end check that per-device
cost_analysis matches a hand-counted matmul."""
import jax
import jax.numpy as jnp
import pytest

from repro.roofline.analysis import (
    HBM_BW,
    LINK_BW,
    PEAK_FLOPS,
    model_flops,
    parse_collectives,
)


def test_parse_collectives_anchored_not_operands():
    hlo = """
  %all-gather.1 = f32[16,1024]{1,0} all-gather(%p0), channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}
  %fusion.2 = f32[64,1024]{1,0} fusion(%all-gather.1), kind=kLoop
  %all-reduce.7 = bf16[512,256]{1,0} all-reduce(%fusion.2), channel_id=2, replica_groups={{0,1}}, to_apply=%add
"""
    out = parse_collectives(hlo)
    assert out["count_by_kind"] == {"all-gather": 1, "all-reduce": 1}
    ag = 16 * 1024 * 4 * (3 / 4)  # result bytes * (n-1)/n
    ar = 2 * 512 * 256 * 2 * (1 / 2)
    assert out["bytes_by_kind"]["all-gather"] == pytest.approx(ag)
    assert out["bytes_by_kind"]["all-reduce"] == pytest.approx(ar)


def test_parse_collectives_iota_groups():
    hlo = "%reduce-scatter.3 = f32[8,128]{1,0} reduce-scatter(%x), replica_groups=[64,8]<=[512], dimensions={0}"
    out = parse_collectives(hlo)
    # ring cost: result * (n-1) with n=8
    assert out["bytes_by_kind"]["reduce-scatter"] == pytest.approx(8 * 128 * 4 * 7)


def test_cost_analysis_matches_hand_count():
    """flops for an unrolled matmul chain == 2*m*k*n each."""

    def f(x, w):
        return jnp.tanh(x @ w) @ w

    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ca = jax.jit(f).lower(x, w).compile().cost_analysis()
    want = 2 * (2 * 64 * 128 * 128)
    assert ca["flops"] == pytest.approx(want, rel=0.05)


def test_model_flops_train_vs_decode():
    from repro.configs import SHAPES, get_config

    cfg = get_config("tinyllama-1.1b")
    tr = model_flops(cfg, SHAPES["train_4k"])
    dec = model_flops(cfg, SHAPES["decode_32k"])
    n = cfg.param_count()
    assert tr == pytest.approx(6 * n * 256 * 4096, rel=1e-6)
    assert dec == pytest.approx(2 * n * 128, rel=1e-6)


def test_moe_active_params_smaller():
    from repro.configs import get_config

    cfg = get_config("deepseek-v2-lite-16b")
    assert cfg.active_param_count() < 0.3 * cfg.param_count()
    # sanity vs the published 16B total / 2.4B active
    assert 10e9 < cfg.param_count() < 22e9
    assert 1.5e9 < cfg.active_param_count() < 4e9


def test_hardware_constants():
    assert PEAK_FLOPS == 197e12 and HBM_BW == 819e9 and LINK_BW == 50e9
