"""Share of the traced window in which no operation ran on the device: one
less the union of the device's busy intervals over the window."""
from chipbench.readers import idle_pct


def read(run):
    return idle_pct(run)
