"""A kernel's share of its roofline in the Kimi Linear cell: the least time
the chip could take for one call — its operations and bytes AT THE HEADS THE
CALL HAS (``sizes['held_heads']``: a chip holds a share of every mixer's
heads) against the peaks (``benchmark/peaks.py``), whichever binds — over
the median device time of the kernel's trace events. ``kda_fwd`` /
``kda_bwd``: ``flops_kimi.kda_kernel_cost`` (the chunked rule's products;
q / k / v / o in the compute dtype, g in float32, the float32 state entering
every chunk written by the forward and read by the backward);
``flash_mla_*``: ``flops_moe.mla_kernel_cost`` through
``flops_kimi.held_mla_kernel_cost``. A program without the kernel gives
nothing."""
import statistics

from benchmark import flops, flops_kimi, peaks
from benchmark import trace as T


def bound(run, kernel):
    sizes = run.config["sizes"]
    seq = run.seq_length()
    rows = run.role.microbatch_rows_per_device(run.args)
    if kernel.startswith("kda_"):
        cost = flops_kimi.kda_kernel_cost(
            kernel, rows, sizes["held_heads"], seq, sizes["kda_head_dim"],
            sizes["kda_head_dim"], sizes["kda_chunk"],
        )
    else:
        cost = flops_kimi.held_mla_kernel_cost(kernel, rows, sizes, seq)
    return flops.roofline_seconds(*cost, peaks.chip_peaks(run.device_kind))


def reduce(run, params):
    if not run.trace:
        return None
    durations = T.op_durations(run.trace, params["kernel"])
    if not durations:
        return None
    least, _which = bound(run, params["kernel"])
    return 100.0 * least / statistics.median(durations)
