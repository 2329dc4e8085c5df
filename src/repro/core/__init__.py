"""SAMP core: quantization numerics, calibrators, the per-layer precision
lattice, the accuracy-decay-aware allocator, and the engine tying them
together (the paper's primary contribution)."""
from repro.core import allocator, calibration, plan, precision, quantize  # noqa: F401
from repro.core.plan import (LayerMode, LayerPlan,  # noqa: F401
                             PrecisionPlan, QuantSpec)
