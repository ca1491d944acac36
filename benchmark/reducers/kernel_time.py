"""Median device microseconds of one call of a named kernel, from the
trace's "XLA Ops" events. For a kernel whose operands XLA may keep in
on-chip memory between ops (the fused add+LayerNorm pair), no HBM roofline
describes the call, so the time itself is the metric: no assumed peak."""
import statistics

from benchmark import trace as T


def reduce(run, params):
    if not run.trace:
        return None
    durations = T.op_durations(run.trace, params["kernel"])
    return statistics.median(durations) * 1e6 if durations else None
