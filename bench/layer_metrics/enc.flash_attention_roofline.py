"""``quant_flash_attention``'s share of its roofline over the traced
window, in percent: each call's least time from its shapes
(``bench/kernels/``) at the chip's peaks, over its device time."""
import readers


def read(run):
    return readers.flash_attention_roofline(run)
