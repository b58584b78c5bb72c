"""Share of the traced window in which no operation ran on the device, in %
(1 minus the union of the device's op intervals over the window)."""

from bench.readers import idle_share


def read(run, summary, e2e):
    return idle_share(summary)
