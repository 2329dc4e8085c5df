"""Mean milliseconds the micro-batcher counted a request queued, from
submission to the flush of its micro-batch (engine + scheduler layer), over
the requests due before the profiler starts."""
import hostphases


def read(run):
    return hostphases.queue_wait_ms(run)
