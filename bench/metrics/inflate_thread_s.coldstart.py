"""Thread-seconds per cold start in CRC and zlib: the summed durations of the
program's ``codec.inflate`` spans (CRC32, decompress and length check of one
chunk) under each ``restore`` span in the window. Moves ``coldstart_s``."""

from bench.program_spans import mean, restores


def read(run, summary, e2e):
    return mean(restores(run), "inflate_thread_s")
