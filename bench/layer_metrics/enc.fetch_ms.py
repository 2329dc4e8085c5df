"""Milliseconds per micro-batch in ``samp.enc.fetch``, the wait for the
device's logits and their copy to the host, from the program's phase
counters over the window (runtime layer)."""
import hostphases


def read(run):
    return hostphases.fetch_ms(run, "enc")
