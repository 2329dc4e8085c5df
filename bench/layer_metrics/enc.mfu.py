"""The whole encode step's share of the chip's peak, in percent: model
operations of the real tokens served in the traced window at peak, over
the host-clock time of those encode calls."""
import readers


def read(run):
    return readers.mfu(run)
