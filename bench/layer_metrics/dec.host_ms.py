"""Host milliseconds per model tick in the decode tick's host phases
(``samp.dec.admit``, ``drain``, ``pages``, ``assemble``, ``dispatch``,
``sample``), from the program's phase counters over the window (runtime
layer)."""
import hostphases


def read(run):
    return hostphases.host_ms(run, "dec")
