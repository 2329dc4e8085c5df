"""Host milliseconds per micro-batch in the encoder step's host phases
(``samp.enc.flush``, ``assemble``, ``pad``, ``dispatch``, ``predict``), from
the program's phase counters over the window (runtime layer)."""
import hostphases


def read(run):
    return hostphases.host_ms(run, "enc")
