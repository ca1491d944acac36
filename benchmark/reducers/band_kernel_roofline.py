"""A band flash kernel's share of its roofline: the least time the chip
could take for one call — operations of the (query tile, key tile) pairs
inside the band for every query head, bytes with q-side tensors at the query
heads' width and k / v and their gradients at the kv heads'
(``benchmark/flops_smallthinker.py``) against the peaks
(``benchmark/peaks.py``), whichever binds — over the median device time of
the kernel's trace events. A program without the kernel gives nothing."""
import statistics

from benchmark import flops, flops_smallthinker, peaks
from benchmark import trace as T


def bound(run, kernel):
    sizes = run.config["sizes"]
    seq = run.seq_length()
    block = min(sizes["attention_block_size"], seq)
    cost = flops_smallthinker.band_kernel_cost(
        kernel, run.role.microbatch_rows_per_device(run.args),
        sizes["num_attention_heads"], sizes["num_key_value_heads"], seq,
        sizes["head_dim"], block, block, sizes["sliding_window_size"],
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
