"""Mean host-clock milliseconds of a ``Runtime.encode`` call: pad, dispatch,
device and ``device_get`` (runtime layer), before the profiler starts."""
import readers


def read(run):
    return readers.step_ms(run)
