"""Host milliseconds per batch inside ``DeviceLoader``'s transfers, each
ending in ``block_until_ready`` (``stats()["h2d_s"]``). Moves
``feed_images_per_s``."""

from bench.readers import ratio


def read(run, summary, e2e):
    return ratio(run.counters["h2d_s_window"], run.counters["h2d_batches_window"], 1e3)
