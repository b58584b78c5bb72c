"""Seconds per cold start inside ``device_put`` and the dequant dispatch, on
the host clock (``ColdStartStats.h2d_s``). Moves ``coldstart_s``."""

from bench.readers import ratio


def read(run, summary, e2e):
    return ratio(run.counters["restore_h2d_s_total"], run.counters["cold_starts"])
