"""``quant_linear``'s share of its roofline over the traced window, in
percent: each call's least time from its shapes (``bench/kernels/``) at
the chip's peaks, over the device time of its trace events."""
import readers


def read(run):
    return readers.quant_linear_roofline(run)
