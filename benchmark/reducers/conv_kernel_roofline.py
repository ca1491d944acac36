"""A short-convolution kernel's share of its roofline: the least time the
chip could take for one call — its HBM bytes (``benchmark/flops_lfm2.py``:
every H-wide operand read once, every result written once) over the peak HBM
bytes/s, which binds by two orders of magnitude over its multiply-adds —
over the device time of the call, the median over the kernel's trace events.

XLA's fast-memory assignment may hold a call's operand or result in on-chip
memory (``S(1)`` in its layout: the forward's ``y`` in the trainer's
program), and such a tensor crosses no HBM: counting it would put the share
over 100 % (127 % read so). A device trace names an op by its HLO text, so
each event says where ITS operands and results live and is held to the
bytes that do cross HBM; the placement differs between the call sites of
one program (the four conv layers) and between compiles. A call with every
tensor on the chip has no HBM bound and is left out of the median — unless
no call crosses HBM, when the share is of the (tiny) compute bound. A
program without the kernel gives nothing."""
import re
import statistics

from benchmark import flops, flops_lfm2, peaks
from benchmark import trace as T

_ARRAY = re.compile(r"\w+\[[\d,]*\]\{[^{}]*\}")


def on_chip_tensors(kernel, event_name):
    """The kernel's tensors (``flops_lfm2.conv_kernel_tensors``) that this
    call holds outside HBM, from its event's HLO text; none where the text
    is not a custom call's (everything is then counted)."""
    results, call, operands = event_name.partition(" custom-call(")
    if not call:
        return frozenset()
    placed = {
        "result": _ARRAY.findall(results),
        "operand": _ARRAY.findall(operands.split("custom_call_target")[0]),
    }
    held = set()
    for name, (_n, side, i) in flops_lfm2.conv_kernel_tensors(kernel).items():
        arrays = placed[side]
        if i < len(arrays) and re.search(r"S\([1-9]\d*\)", arrays[i]):
            held.add(name)
    return frozenset(held)


def bound(run, kernel, on_chip=frozenset()):
    cost = flops_lfm2.conv_kernel_cost(
        kernel, run.role.microbatch_rows_per_device(run.args),
        run.seq_length(), run.config["sizes"]["hidden_size"],
        on_chip=on_chip,
    )
    return flops.roofline_seconds(*cost, peaks.chip_peaks(run.device_kind))


def shares(run, kernel):
    """(least time / device time, which bound) of every event of the
    kernel."""
    found = []
    for lines in run.trace.values():
        for name, _start, duration in lines.get(T.OPS, []):
            if T.op_name(name) == kernel and duration > 0:
                least, which = bound(
                    run, kernel, on_chip_tensors(kernel, name)
                )
                found.append((least / (duration / 1e9), which))
    return found


def reduce(run, params):
    if not run.trace:
        return None
    found = shares(run, params["kernel"])
    if not found:
        return None
    crossing = [share for share, which in found if which == "memory"]
    return 100.0 * statistics.median(
        crossing or [share for share, _which in found]
    )
