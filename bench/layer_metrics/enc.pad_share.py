"""Padded token slots over all token slots of the window's encode calls, in
percent, from ``Runtime.stats`` (engine + scheduler layer)."""
import readers


def read(run):
    return readers.pad_share(run)
