"""Median, over the window's step records, of one named span of the flight
recorder's span tree (``step.record`` events of ``telemetry/steps.py``,
traced runs): per record the total duration of the spans called ``name``
(``spans`` entries are ``[name, parent, t0_s, t1_s]``, or ``[..., count,
total_s]`` where repeats were folded), children included — or, with
``self: true``, the name's SELF time (``phases[name]``: its duration minus
what its children cover). ``stepped``: only the records that did (true) or
did not (false) make a global step; left out, every record. A record
without the span does not count; a program whose records carry no span tree
gives nothing."""
from benchmark.rundata import median_ms


def reduce(run, params):
    name, stepped = params["name"], params.get("stepped")
    values = []
    for rec in run.step_records:
        spans = rec.get("spans")
        if not spans or (
            stepped is not None and bool(rec.get("stepped")) != bool(stepped)
        ):
            continue
        named = [s for s in spans if s[0] == name]
        if not named:
            continue
        if params.get("self"):
            values.append((rec.get("phases") or {}).get(name, 0.0))
        else:
            values.append(sum(
                s[5] if len(s) > 4 else s[3] - s[2] for s in named
            ))
    return median_ms(values)
