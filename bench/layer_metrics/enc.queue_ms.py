"""Mean milliseconds from a request's due time to the start of the
``Runtime.encode`` call that served it (engine + scheduler layer), over
the requests due before the profiler starts."""
import readers


def read(run):
    return readers.queue_ms(run, "served")
