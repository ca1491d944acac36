"""A flash kernel's share of its roofline in a configuration whose layer
kinds differ in their head count: the least time the chip could take for
one call — operations of the visited (query tile, key tile) pairs for every
query head OF THE KIND that runs the kernel, bytes with q-side tensors at
that width and k / v and their gradients at the kv heads'
(``benchmark/flops_laguna.kernel_cost``: ``flash_band_*`` the sliding
layers', ``flash_gqa_*`` the full ones') against the peaks
(``benchmark/peaks.py``), whichever binds — over the median device time of
the kernel's trace events. A program without the kernel gives nothing."""
import statistics

from benchmark import flops, flops_laguna, peaks
from benchmark import trace as T


def bound(run, kernel):
    cost = flops_laguna.kernel_cost(
        kernel, run.role.microbatch_rows_per_device(run.args),
        run.config["sizes"], run.seq_length(),
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
