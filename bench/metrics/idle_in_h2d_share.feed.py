"""Share of device 0's idle time in the window whose gap midpoint lies in a
``device_loader.h2d`` span of the feeder, in %: each idle gap is named by the
feeder's span that holds its midpoint (``device_loader.h2d``,
``device_loader.dequant``, ``device_loader.put_wait``, ``loader.wait``, or
none), through ``bench.trace.summarize``. The idle seconds of every state
are kept in the run's counters. Moves ``feed_images_per_s``."""

from bench.program_spans import feeder_idle
from bench.readers import ratio


def read(run, summary, e2e):
    idle = feeder_idle(run)
    if idle is None:
        return None
    for state, s in idle.items():
        run.counters["idle_s." + state] = s
    return ratio(idle.get("device_loader.h2d", 0.0), sum(idle.values()), 100.0)
