"""A block-diffusion flash kernel's share of its roofline: the least time
the chip could take for one call — operations of the (query tile, key tile)
pairs that hold a visible pair of the two-stream block rule for every query
head, bytes with q-side tensors at the query heads' width and k / v and
their gradients at the kv heads' over both streams' positions
(``benchmark/flops_sdar.py``) against the peaks (``benchmark/peaks.py``),
whichever binds — over the median device time of the kernel's trace events.
A program without the kernel gives nothing."""
import statistics

from benchmark import flops, flops_sdar, peaks
from benchmark import trace as T


def bound(run, kernel):
    sizes = run.config["sizes"]
    length = run.seq_length()  # ONE stream's positions
    block = min(sizes["attention_block_size"], length)
    cost = flops_sdar.bd_kernel_cost(
        kernel, run.role.microbatch_rows_per_device(run.args),
        sizes["num_attention_heads"], sizes["num_key_value_heads"], length,
        sizes["head_dim"], block, block, sizes["block_length"],
    )
    return flops.roofline_seconds(*cost, peaks.chip_peaks(run.device_kind))


def reduce(run, params):
    if not run.trace:
        return None
    durations = T.op_durations(run.trace, params["kernel"])
    if not durations:
        return None
    least, _which = bound(run, params["kernel"])
    return 100.0 * least / statistics.median(durations)
