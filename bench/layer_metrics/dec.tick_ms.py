"""Mean host-clock milliseconds of a ``ServeEngine.step`` that ran the
model (runtime layer), before the profiler starts."""
import readers


def read(run):
    return readers.step_ms(run)
