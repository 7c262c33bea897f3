"""Layer `device`: 1 - union of the `XLA Ops` intervals over the traced
window, in percent."""

from benchmarks import xplane


def read(run):
    return xplane.idle_share_percent(run["capture"])
