"""Mean milliseconds from a request's due time to the start of the engine
tick that admitted it into a slot (engine + scheduler layer), over the
requests due before the profiler starts."""
import readers


def read(run):
    return readers.queue_ms(run, "admitted")
