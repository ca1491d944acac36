"""Median wall of the window's ``CollaborativeOptimizer.step`` calls, all
peers together: ``stepped: true`` for the calls that made a global step (the
time the training loop is blocked in the boundary), false for the progress
reports in between. Needs at least ``min_count`` calls."""
from benchmark.rundata import median_ms


def reduce(run, params):
    walls = run.opt_calls_in_window(bool(params["stepped"]))
    if len(walls) < int(params.get("min_count", 1)):
        return None
    return median_ms(walls)
