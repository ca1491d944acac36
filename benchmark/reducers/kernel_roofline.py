"""A Pallas kernel's share of its roofline: the least time the chip could
take for one call (the larger of operations over peak FLOP/s and bytes over
peak HBM bytes/s, from ``benchmark/flops.py`` and ``benchmark/peaks.py``)
over the median device time of the kernel's trace events. Only for kernels
whose operands come from HBM (the flash pair); the fused add+LayerNorm pair
reports its time instead (``kernel_time``)."""
import statistics

from benchmark import flops, peaks
from benchmark import trace as T


def bound(run, kernel):
    sizes = run.config["sizes"]
    heads = sizes["num_attention_heads"]
    cost = flops.kernel_cost(
        kernel, run.role.microbatch_rows_per_device(run.args), heads,
        run.seq_length(), sizes["hidden_size"] // heads,
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
