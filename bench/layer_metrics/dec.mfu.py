"""The whole decode step's share of the chip's peak, in percent: model
operations of the tokens of the ticks in the traced window at peak, over
the host-clock time of those ticks."""
import readers


def read(run):
    return readers.mfu(run)
