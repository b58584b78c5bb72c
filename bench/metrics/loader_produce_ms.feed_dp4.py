"""Host milliseconds per global batch that ``DataLoader`` spent producing
it (the program's ``loader.produce`` spans in the window, over the global
batches the feeder took from the host loader: its ``loader.wait`` spans).
Moves ``feed_images_per_s``."""

from bench.program_spans import per_batch_ms


def read(run, summary, e2e):
    return per_batch_ms(run, "loader.produce")
