"""Host milliseconds per batch that the device feeder waited on the host
loader's queue (the program's ``loader.wait`` spans in the window, over
their count): the most a faster loader can buy. Moves
``feed_images_per_s``."""

from bench.program_spans import per_batch_ms


def read(run, summary, e2e):
    return per_batch_ms(run, "loader.wait")
