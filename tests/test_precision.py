"""Per-layer precision lattice + execution-plan grouping."""
from _hypothesis_shim import hypothesis, st
import pytest

from repro.configs import get_config
from repro.core.plan import LayerMode, PrecisionPlan
from repro.core.precision import make_policy, paper_grid
from repro.models.transformer import build_plan

settings = hypothesis.settings(max_examples=30, deadline=None)


def test_modes():
    assert not LayerMode.FLOAT.quant_ffn
    assert LayerMode.QUANT_FFN_ONLY.quant_ffn
    assert not LayerMode.QUANT_FFN_ONLY.quant_mha
    assert LayerMode.FULLY_QUANT.quant_mha and LayerMode.FULLY_QUANT.quant_ffn


def test_prefix_policy_counts():
    p = PrecisionPlan.prefix(12, 5, LayerMode.FULLY_QUANT)
    assert p.num_quant_mha == 5 and p.num_quant_ffn == 5
    p2 = PrecisionPlan.prefix(12, 7, LayerMode.QUANT_FFN_ONLY)
    assert p2.num_quant_mha == 0 and p2.num_quant_ffn == 7
    with pytest.raises(ValueError):
        PrecisionPlan.prefix(12, 13, LayerMode.FLOAT)


def test_paper_grid_size():
    grid = paper_grid(12)
    # float + 2 modes x 12 ks
    assert len(grid) == 1 + 2 * 12
    grid2 = paper_grid(12, stride=2)
    assert len(grid2) == 1 + 2 * 6


def test_group_boundaries_partition():
    p = PrecisionPlan.prefix(10, 4, LayerMode.FULLY_QUANT)
    runs = p.group_boundaries()
    assert runs[0] == (0, 4, LayerMode.FULLY_QUANT)
    assert runs[1] == (4, 10, LayerMode.FLOAT)


@settings
@hypothesis.given(st.integers(1, 26), st.integers(0, 26))
def test_plan_covers_all_layers_every_arch(n_unused, k):
    for arch in ("deepseek-coder-33b", "gemma2-2b", "recurrentgemma-9b",
                 "xlstm-125m", "deepseek-v2-236b"):
        cfg = get_config(arch)
        k_eff = min(k, cfg.num_layers)
        policy = PrecisionPlan.prefix(cfg.num_layers, k_eff,
                                      LayerMode.QUANT_FFN_ONLY)
        plan = build_plan(cfg, policy)
        covered = []
        for g in plan:
            assert g.stop - g.start == g.steps * len(g.kinds)
            covered.extend(range(g.start, g.stop))
        assert covered == list(range(cfg.num_layers))


def test_plan_scans_homogeneous_archs():
    cfg = get_config("deepseek-coder-33b")
    policy = PrecisionPlan.prefix(cfg.num_layers, 10,
                                  LayerMode.QUANT_FFN_ONLY)
    plan = build_plan(cfg, policy)
    assert len(plan) == 2                     # quantized prefix + float rest
    assert all(g.scan for g in plan)


def test_plan_period_scan_gemma2():
    cfg = get_config("gemma2-2b")             # alternating local/global
    policy = PrecisionPlan.full_float(cfg.num_layers)
    plan = build_plan(cfg, policy)
    assert len(plan) == 1
    assert len(plan[0].kinds) == 2            # one period = 2 layers
    assert plan[0].steps == 13


def test_plan_dsv2_dense_first_layer():
    cfg = get_config("deepseek-v2-236b")
    plan = build_plan(cfg, PrecisionPlan.full_float(cfg.num_layers))
    assert len(plan) == 2
    assert plan[0].stop - plan[0].start == 1  # the dense-FFN layer 0
    assert not plan[0].kinds[0].moe
    assert plan[1].kinds[0].moe and plan[1].steps == 59


def test_make_policy_names():
    cfg = get_config("qwen2-0.5b")
    assert make_policy(cfg, "float").num_quant_ffn == 0
    assert make_policy(cfg, "ffn").num_quant_ffn == cfg.num_layers
    assert make_policy(cfg, "full8").num_quant_mha == 8
    with pytest.raises(ValueError):
        make_policy(cfg, "int4")
