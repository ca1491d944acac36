"""Median of one flight-recorder phase over the window's stepping
boundaries (``step.record`` events of ``telemetry/steps.py``; traced runs
only, because recording adds a ``block_until_ready`` per boundary)."""
from benchmark.rundata import median_ms


def reduce(run, params):
    values = [
        rec["phases"][params["phase"]]
        for rec in run.step_records
        if rec.get("stepped") and params["phase"] in (rec.get("phases") or {})
    ]
    return median_ms(values)
