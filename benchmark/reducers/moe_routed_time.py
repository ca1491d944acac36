"""Device milliseconds of the routed experts' path per ``accumulate_step``
execution, from the trace's "XLA Ops" events.

A device trace names an op by its HLO text and carries no scope, so the
path's ops are found by what only they are (``parallel/moe.routed_experts``):
the ``sort`` ops (the top-k over the router's scores, the sort of the slots
by expert) and the ``while`` loops over row tiles, forward and backward,
which alone carry the HELD experts' stacked matrices ``[held, H, F]`` of ONE
layer in their state (the scan over layers carries them stacked once more).
A tile loop's event spans its body: the gather of a tile's rows, the three
matmuls, the scatter-add of the combine. A program without such ops (one
older than the routed layer) gives nothing."""
from benchmark import trace as T


def routed_events(run):
    sizes = run.config["sizes"]
    held = f"[{sizes['held_experts']},{sizes['hidden_size']}," \
           f"{sizes['moe_intermediate_size']}]"
    found = []
    for lines in run.trace.values():
        for name, _start, duration in lines.get(T.OPS, []):
            op = T.op_name(name)
            if op == "sort" or (op == "while" and held in name):
                found.append((op, duration / 1e9))
    return found


def reduce(run, params):
    if not run.trace:
        return None
    events = routed_events(run)
    executions = sum(
        len(d) for d in T.module_durations(
            run.trace, [run.program("accumulate")]
        ).values()
    )
    if not events or not executions:
        return None
    return sum(seconds for _op, seconds in events) / executions * 1e3
