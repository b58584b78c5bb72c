"""The window's device arithmetic, the dequant's multiply and add per
element of each call in the trace (``costs.elementwise_cost``), over the
window at the chips' bf16 peak, in %: the whole window's share of the
chip, which bounds what the kernel's roofline share can buy end to end."""

from bench.readers import window_mfu


def read(run, summary, e2e):
    return window_mfu(run, summary, "dequant")
