"""Host milliseconds per batch that ``DataLoader`` spent producing it
(gather into the staging buffer; ``stats()["loader_produce_s"]``). Moves
``feed_images_per_s``."""

from bench.readers import ratio


def read(run, summary, e2e):
    return ratio(run.counters["loader_produce_s_window"], run.counters["batches_window"], 1e3)
