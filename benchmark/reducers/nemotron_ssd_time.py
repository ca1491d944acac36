"""Device milliseconds per ``accumulate_step`` execution of every trace
event whose op is named ``ssd_*`` (``ops/ssd.py``'s Pallas kernels: the
forward, a remat replay of it where the layer policy keeps no output, the
backward; all Mamba layers together). A program without such ops gives
nothing."""
from benchmark import trace as T


def reduce(run, params):
    if not run.trace:
        return None
    seconds = [
        duration / 1e9 for lines in run.trace.values()
        for name, _start, duration in lines.get(T.OPS, [])
        if T.op_name(name).startswith("ssd_")
    ]
    executions = sum(
        len(d) for d in T.module_durations(
            run.trace, [run.program("accumulate")]
        ).values()
    )
    if not seconds or not executions:
        return None
    return sum(seconds) / executions * 1e3
