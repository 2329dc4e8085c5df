"""``decode_attention``'s share of its roofline over the traced window, in
percent: each call's least time for the live context of the traced
ticks (``bench/kernels/``) at the chip's peaks, over its device time."""
import readers


def read(run):
    return readers.decode_attention_roofline(run)
