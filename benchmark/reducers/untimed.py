"""Share of the window's recorded wall that no span of the flight
recorder covers: sum of ``untimed_s`` over sum of wall (``dur_s``) of the
window's ``step.record`` events, all peers together — the tracing's own
coverage. Only records that carry the span tree count: a program without it
gives nothing."""


def reduce(run, params):
    records = [rec for rec in run.step_records if rec.get("spans")]
    wall = sum(rec.get("dur_s", 0.0) for rec in records)
    if wall <= 0:
        return None
    return 100.0 * sum(rec.get("untimed_s", 0.0) for rec in records) / wall
