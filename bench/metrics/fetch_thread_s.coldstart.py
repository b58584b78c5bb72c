"""Thread-seconds per cold start on the wire: the summed durations of the
program's ``codec.fetch`` spans (each a positioned read of one stored chunk)
under each ``restore`` span in the window. Moves ``coldstart_s``."""

from bench.program_spans import mean, restores


def read(run, summary, e2e):
    return mean(restores(run), "fetch_thread_s")
