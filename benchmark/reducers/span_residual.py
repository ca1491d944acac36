"""Median, over the window's step records, of what is left of one span
after other spans are taken out of it: per record the total of the spans
called ``of`` minus the totals of the spans in ``names`` (a folded entry
counts its ``total_s``, as in ``reducers/span.py``), floored at 0.
``stepped`` as there. A record without the ``of`` span does not count, and
neither does one that carries NONE of ``names``: a program that does not
record them (the parent of the PR that added them) gives nothing, not the
whole span under the residual's name."""
from benchmark.rundata import median_ms


def _total(spans, name):
    return sum(
        s[5] if len(s) > 4 else s[3] - s[2] for s in spans if s[0] == name
    )


def reduce(run, params):
    of, names, stepped = params["of"], set(params["names"]), params.get("stepped")
    values = []
    for rec in run.step_records:
        spans = rec.get("spans") or []
        if stepped is not None and bool(rec.get("stepped")) != bool(stepped):
            continue
        present = {s[0] for s in spans}
        if of not in present or not (names & present):
            continue
        values.append(max(
            0.0, _total(spans, of) - sum(_total(spans, n) for n in names)
        ))
    return median_ms(values)
