"""A share of the window's wall, in percent, off the flight recorder's
``step.record`` events, all peers together: the sum of the record field
``params["field"]`` (``held_excess_s``, the spans' overrun; ``cpu_s``, the
loop thread's CPU: read on the record's own thread by ``telemetry/steps.py``)
over the sum of the records' wall. Only records that carry the host counters
count: a program without them (no ``cpu_s`` on its records) gives nothing."""


def reduce(run, params):
    records = [rec for rec in run.step_records if "cpu_s" in rec]
    wall = sum(rec.get("dur_s", 0.0) for rec in records)
    if wall <= 0:
        return None
    return 100.0 * sum(rec.get(params["field"], 0.0) for rec in records) / wall
