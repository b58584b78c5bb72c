"""Share of the traced window in which no operation ran on a device, in %,
the mean over the mesh's devices (1 minus the union of each device's op
intervals over the window). Moves ``feed_images_per_s``."""

from bench.readers import idle_share


def read(run, summary, e2e):
    return idle_share(summary)
