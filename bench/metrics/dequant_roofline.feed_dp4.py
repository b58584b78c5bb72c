"""The dequant kernel's least time by its roofline over its device time,
summed over its calls on every device in the trace, in %: per call the u8
codes read, the output written and the float32 scale and bias read, over
819 GB/s, against two operations per element over the bf16 peak
(``costs.elementwise_cost``, ``costs.roofline_time``)."""

from bench.readers import roofline_share


def read(run, summary, e2e):
    return roofline_share(run, summary, "dequant")
