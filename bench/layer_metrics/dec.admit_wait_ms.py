"""Mean milliseconds the slot scheduler counted a request queued, from
submission to its admission into a slot (engine + scheduler layer), over
the requests due before the profiler starts."""
import hostphases


def read(run):
    return hostphases.queue_wait_ms(run)
