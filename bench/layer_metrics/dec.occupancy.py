"""Mean live slots over slots per model tick, in percent (engine +
scheduler layer), over the ticks before the profiler starts."""
import readers


def read(run):
    return readers.occupancy(run)
