"""Host milliseconds per global batch in the ``device_put`` calls of each
device's block, summed over the devices (the program's
``device_loader.shard_put`` spans in the window, one per device a batch,
over the global batches the feeder took). Beside ``h2d_ms.feed_dp4``, which
holds them and the wait for the transfers: near it, the host's part of the
transfers runs one device after another and sets the pace. Nothing is read
from a program that records no such span. Moves ``feed_images_per_s``."""

from bench.program_spans import named, per_batch_ms, program_spans

SPAN = "device_loader.shard_put"


def read(run, summary, e2e):
    spans = program_spans(run)
    if not spans or not named(spans, SPAN):
        return None
    return per_batch_ms(run, SPAN)
