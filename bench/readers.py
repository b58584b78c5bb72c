"""What the per-layer metric readers share: the chip's peaks, and a guard
against dividing by a time that was not measured."""

from __future__ import annotations

from typing import Optional


def peak() -> dict:
    """The published peaks of the chip this process runs on."""
    import jax

    from bench.peaks import peak as lookup

    return lookup(jax.devices()[0].device_kind)


def ratio(num: float, den: float, scale: float = 1.0) -> Optional[float]:
    """``num / den * scale``, or nothing where ``den`` was not measured."""
    if not den or den <= 0:
        return None
    return num / den * scale


def kernel_costs(run, summary, kernel: str):
    """(operations, bytes, device seconds) of each traced call of ``kernel``,
    its shapes read from the call's HLO instruction."""
    from bench.costs import elementwise_cost

    per_element = run.cell.traffic["kernels"][kernel]["ops_per_element"]
    return [(*elementwise_cost(name, per_element), s) for name, s in summary.kernel_calls[kernel]]


def roofline_share(run, summary, kernel: str) -> Optional[float]:
    """The kernel's least time by its roofline over its device time, summed
    over its calls in the window, in %. The bound that sets each call's
    least time is counted in the run's counters."""
    if summary is None or not summary.kernel_calls.get(kernel):
        return None
    from bench.costs import roofline_time

    least, spent = 0.0, 0.0
    for ops, nbytes, s in kernel_costs(run, summary, kernel):
        t, bound = roofline_time(ops, nbytes, peak())
        least, spent = least + t, spent + s
        key = f"{kernel}_calls_{bound}_bound"
        run.counters[key] = run.counters.get(key, 0) + 1
    return ratio(least, spent, 100.0)


def window_mfu(run, summary, kernel: str) -> Optional[float]:
    """The operations of ``kernel``'s calls in the window over the window at
    the chips' bf16 peak, in %."""
    if summary is None:
        return None
    ops = sum(o for o, _, _ in kernel_costs(run, summary, kernel))
    return ratio(ops, summary.window_s * run.cell.chips * peak()["bf16_flops_per_s"], 100.0)


def idle_share(summary) -> Optional[float]:
    if summary is None:
        return None
    return 100.0 * summary.idle_share
