"""The window's device arithmetic, the dequant's multiply and add per
element of each call on every device in the trace
(``costs.elementwise_cost``), over the window at the bf16 peak of the
cell's chips, in %."""

from bench.readers import window_mfu


def read(run, summary, e2e):
    return window_mfu(run, summary, "dequant")
