"""Device milliseconds per ``accumulate_step`` execution of one of the two
passes over blocks of query rows that the selected-attention decoder adds to
a layer (``models/keye_vl2.py``), from the trace's "XLA Ops" events.

A device trace names an op by its HLO text and carries no scope, so a pass
is found by what only it is. Both are ``lax.map``s — ``while`` loops — over
blocks of query rows whose state holds the layer's int8 selection cut into
THEIR blocks, ``s8[blocks, rows, S]`` with ``rows`` the program's
``INDEX_BLOCK_ROWS`` (``select``: the index-score pass and the exact top-k,
which WRITES the selection a block a step) or ``INDEX_LOSS_BLOCK_ROWS``
(``index_loss``: the indexer's loss, forward and — a second loop over the
same blocks — backward, which READ it). The shapes tell the two apart ONLY
while the two block sizes differ: where a later change sets them equal (or
a row is as short as a block) both passes give NOTHING, rather than each
the other's time too. The loop's event spans its body (the nested
bisection loops of the top-k carry a block's keys, ``u32[rows, S]``, not the
selection: they are inside the event, not counted again). The routed
experts' loops carry the held matrices and no ``s8`` array, and the router's
``sort`` is no ``while``: neither is counted here, and
``moe_routed_time`` counts neither of these. A program without such a loop,
or without the model, gives nothing."""
from benchmark import trace as T

def block_rows(seq: int):
    """{pass: query rows a block} at rows of ``seq``, from the program; None
    where the program has no such model or the two coincide."""
    try:
        import dedloc_tpu.models.keye_vl2 as keye_vl2
    except ImportError:
        return None
    rows = {
        "select": min(keye_vl2.INDEX_BLOCK_ROWS, seq),
        "index_loss": min(keye_vl2.INDEX_LOSS_BLOCK_ROWS, seq),
    }
    return None if rows["select"] == rows["index_loss"] else rows


def loop_events(run, which: str):
    seq = run.seq_length()
    by_pass = block_rows(seq)
    if by_pass is None:
        return []
    rows = by_pass[which]
    batch = run.role.microbatch_rows_per_device(run.args)
    carried = f"s8[{batch * seq // rows},{rows},{seq}]"
    return [
        duration / 1e9
        for lines in run.trace.values()
        for name, _start, duration in lines.get(T.OPS, [])
        if T.op_name(name) == "while" and carried in name
    ]


def reduce(run, params):
    if not run.trace:
        return None
    events = loop_events(run, params["pass"])
    executions = sum(
        len(d) for d in T.module_durations(
            run.trace, [run.program("accumulate")]
        ).values()
    )
    if not events or not executions:
        return None
    return sum(events) / executions * 1e3
