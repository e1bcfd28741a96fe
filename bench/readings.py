"""Arithmetic the metric readers share."""
from __future__ import annotations


def kernel_roofline(run, names, total_work) -> dict:
    """A kernel's share of its roofline in %: the least time its calls
    in the trace could take on this chip, over their device time.
    ``total_work(calls)`` is the ``work.Work`` of all the calls in the
    traced window; the bound that decides is reported beside the
    share."""
    from bench import work
    if run.trace is None:
        return None
    secs, calls = run.trace.kernel_time(names)
    if not calls:
        return None
    total = total_work(calls)
    if not total.flops:
        return None
    least, bound = work.roofline_s(total, run.peak)
    return {"value": 100.0 * least / secs, "bound": bound, "calls": calls}
