from repro.quant import ptq  # noqa: F401
from repro.quant.ptq import apply_plan, capture_stats, quantize_weight

__all__ = ["ptq", "apply_plan", "capture_stats", "quantize_weight"]
