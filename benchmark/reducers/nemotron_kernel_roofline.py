"""A kernel's share of its roofline in the Nemotron-H cell: the least time
the chip could take for one call — its operations and bytes AT THE HEADS THE
CALL HAS (``sizes['held_mamba_heads']`` in ``sizes['held_groups']`` groups;
``sizes['held_heads']`` query heads over ``sizes['held_kv_heads']`` key
heads: a chip holds a share of every mixer's heads, and the published 32 / 2
here would read twice the work) against the peaks (``benchmark/peaks.py``),
whichever binds — over the median device time of the kernel's trace events.
``ssd_fwd`` / ``ssd_bwd``: ``flops_nemotron.ssd_kernel_cost`` (the chunked
scan's products; x / y / B / C in the compute dtype, dt and the log-decays
in float32, the float32 state entering every chunk written by the forward
and read by the backward); ``flash_gqa_fwd`` / ``flash_gqa_bwd_tiled``:
``flops_nemotron.held_gqa_kernel_cost`` (the triangle's visited tiles; the
one-sweep backward at 5 matmuls a tile). A program without the kernel gives
nothing."""
import statistics

from benchmark import flops, flops_nemotron, peaks
from benchmark import trace as T


def bound(run, kernel):
    sizes = run.config["sizes"]
    seq = run.seq_length()
    rows = run.role.microbatch_rows_per_device(run.args)
    if kernel.startswith("ssd_"):
        cost = flops_nemotron.ssd_kernel_cost(
            kernel, rows, sizes["held_mamba_heads"], sizes["held_groups"],
            seq, sizes["mamba_head_dim"], sizes["ssm_state_size"],
            sizes["ssd_chunk"],
        )
    else:
        cost = flops_nemotron.held_gqa_kernel_cost(kernel, rows, sizes, seq)
    return flops.roofline_seconds(*cost, peaks.chip_peaks(run.device_kind))


def reduce(run, params):
    if not run.trace:
        return None
    durations = T.op_durations(run.trace, params["kernel"])
    if not durations:
        return None
    least, _which = bound(run, params["kernel"])
    return 100.0 * least / statistics.median(durations)
