"""Share of the traced window, in percent, in which the device sits idle
while the host is in an encoder phase other than ``samp.enc.fetch``
(device layer)."""
import hostphases


def read(run):
    return hostphases.host_bound_share(run, "enc")
