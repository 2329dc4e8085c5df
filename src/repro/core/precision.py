"""The paper's per-layer mixed-precision lattice (§3.2) as named plans.

SAMP divides each Transformer layer's GEMMs into the MHA group and the FFN
group, yielding three per-layer modes (paper Figure 2, :class:`LayerMode`):

* ``FLOAT``           — no quantization (FP32/FP16/bf16 GEMMs)
* ``QUANT_FFN_ONLY``  — FFN GEMMs int8, MHA stays float (paper's preferred)
* ``FULLY_QUANT``     — MHA and FFN GEMMs both int8

The paper's search space is "quantize the first k layers in mode m"
(prefix plans, :meth:`PrecisionPlan.prefix`); the beyond-paper extension
allows arbitrary subsets (:meth:`PrecisionPlan.subset`). Both build
:class:`~repro.core.plan.PrecisionPlan`\\ s, the one precision description.
"""
from __future__ import annotations

import re

from repro.core.plan import LayerMode, PrecisionPlan

# bench/system.py's name for PrecisionPlan; ROADMAP debt "precision residue"
EncoderPolicy = PrecisionPlan


def make_policy(cfg, name: str, float_dtype: str = "bfloat16",
                **mode_kw) -> PrecisionPlan:
    """Named plans: 'float' (the float baseline), 'ffn' (all layers
    QUANT_FFN_ONLY), 'full' (all FULLY_QUANT), 'ffnK'/'fullK' (first K).
    ``mode_kw`` (``dynamic_acts``, ``calibrator``) goes to
    :meth:`LayerPlan.for_mode`."""
    m = re.fullmatch(r"(float|ffn|full)(\d+)?", name)
    if not m:
        raise ValueError(f"bad policy name {name!r}")
    kind, k = m.group(1), m.group(2)
    n = cfg.num_layers
    if kind == "float":
        return PrecisionPlan.full_float(n, float_dtype)
    mode = (LayerMode.QUANT_FFN_ONLY if kind == "ffn"
            else LayerMode.FULLY_QUANT)
    return PrecisionPlan.prefix(n, int(k) if k else n, mode, float_dtype,
                                **mode_kw)


def paper_grid(num_layers: int, float_dtype: str = "bfloat16",
               stride: int = 1, **mode_kw
               ) -> list[tuple[str, int, PrecisionPlan]]:
    """The paper's full candidate grid: (mode_name, k, plan) for both modes
    and every k in 0..N (Table 2 shows k in steps of 2; ``stride`` controls
    that). Equivalent sweep points are deduped: k=0 in either mode IS the
    Fully-FP16(bf16) baseline (every mode's empty prefix collapses to the
    same all-float plan), so the grid carries it exactly once and
    ``SAMPEngine.sweep`` never evaluates a duplicate candidate."""
    grid: list[tuple[str, int, PrecisionPlan]] = [
        ("float", 0, PrecisionPlan.full_float(num_layers, float_dtype))]
    seen = {grid[0][2]}
    for mode in (LayerMode.FULLY_QUANT, LayerMode.QUANT_FFN_ONLY):
        for k in range(0, num_layers + 1, stride):
            plan = PrecisionPlan.prefix(num_layers, k, mode, float_dtype,
                                        **mode_kw)
            if plan in seen:
                continue
            seen.add(plan)
            grid.append((mode.value, k, plan))
    return grid
