"""JAX's own compile events before the window opens, the WHOLE process's
(``Recorder.compiles``: the harness's reference check and scratch analysis
as well as the role's start). ``kinds``: the seconds of those
``/jax/core/compile/<kind>`` events, summed as they are (a trace nested in
a trace counts twice: the set-up record's own sums keep the outermost).
``traces_of``: how many times that logical program of the cell was TRACED
(its name with or without ``jit(...)``, as ``run.py`` matches names; JAX
emits the event around a cached trace too, in microseconds: only an event
of ``TRACE_MIN_S`` or more is a trace — the set-up record's own rule, stated
once, there; a program without the record gives no count)."""

try:
    from dedloc_tpu.telemetry.steps import TRACE_MIN_S
except ImportError:
    TRACE_MIN_S = None


def reduce(run, params):
    start, _end = run.window()
    before = [c for c in run.recorder.compiles if c[0] <= start]
    if "traces_of" in params:
        if TRACE_MIN_S is None:
            return None
        program = run.program(params["traces_of"])
        return float(sum(
            1 for _t, kind, fun, seconds in before
            if kind == "jaxpr_trace_duration" and seconds >= TRACE_MIN_S
            and fun.removeprefix("jit(").removesuffix(")") == program
        ))
    if not before:
        return None
    return sum(s for _t, kind, _f, s in before if kind in params["kinds"])
