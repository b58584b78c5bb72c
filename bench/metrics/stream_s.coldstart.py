"""Seconds per cold start from a restore's first chunk fetch to its last
chunk inflate (the program's ``codec.fetch`` and ``codec.inflate`` spans
under each ``restore`` span in the window): the wall time of the stream.
``coldstart_s`` minus this is the cold start's time outside the stream.
The mean of every other part of a restore (``program_spans.restores``) is
kept in the run's counters. Moves ``coldstart_s``."""

from bench.program_spans import mean, restores


def read(run, summary, e2e):
    rows = restores(run)
    for part in rows[0] if rows else ():
        run.counters["restore." + part] = mean(rows, part)
    return mean(rows, "stream_s")
