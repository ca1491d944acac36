"""Device time of named programs from the trace's "XLA Modules" line.

``per: execution`` — median device milliseconds of one execution (all
devices together). ``per: apply`` — total device time of the listed programs
on the busiest device over the number of optimizer applies traced there
(``count_programs``), i.e. device milliseconds per peer per global step."""
import statistics

from benchmark import trace as T


def reduce(run, params):
    if not run.trace:
        return None
    names = [run.program(p) for p in params["programs"]]
    per_device = T.module_durations(run.trace, names)
    if params.get("per", "execution") == "execution":
        durations = [d for ds in per_device.values() for d in ds]
        return statistics.median(durations) * 1e3 if durations else None
    counted = T.module_durations(
        run.trace, [run.program(p) for p in params["count_programs"]]
    )
    best = None
    for device, durations in per_device.items():
        applies = len(counted.get(device, []))
        if applies and durations:
            value = sum(durations) / applies * 1e3
            best = value if best is None else max(best, value)
    return best
