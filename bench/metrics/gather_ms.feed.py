"""Host milliseconds per batch in ``RaDataset.gather`` and, for an
unshuffled feed, ``RaDataset.rows`` (the program's ``dataset.gather`` and
``dataset.rows`` spans in the window, over the batches the feeder took
from the host loader). Moves ``feed_images_per_s``."""

from bench.program_spans import per_batch_ms


def read(run, summary, e2e):
    return per_batch_ms(run, "dataset.gather", "dataset.rows")
