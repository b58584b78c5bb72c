"""Host milliseconds per global batch inside ``DeviceLoader``'s transfers
(the program's ``device_loader.h2d`` spans in the window: every device's
puts and one wait for all of them), over the global batches the feeder
took. Moves ``feed_images_per_s``."""

from bench.program_spans import per_batch_ms


def read(run, summary, e2e):
    return per_batch_ms(run, "device_loader.h2d")
