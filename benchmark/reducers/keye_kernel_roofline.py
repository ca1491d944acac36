"""A selected flash kernel's share of its roofline: the least time the chip
could take for one call — operations of the (query tile, key tile) pairs on
and under the diagonal that hold a selected pair, for every query head;
bytes with q-side tensors at the query heads' width, k / v and their
gradients at the kv heads', and those tiles of the int8 selection
(``benchmark/flops_keye.sel_kernel_cost``) against the peaks
(``benchmark/peaks.py``), whichever binds — over the median device time of
the kernel's trace events. The share of the triangle's tiles that hold a
selected pair is DATA: the program's own gauge ``attn.select_tile_share`` on
the window's step records (their mean; 1 — every tile — where a record
lacks it). A program without the kernel gives nothing."""
import statistics

from benchmark import flops, flops_keye, peaks
from benchmark import trace as T


def tile_share(run) -> float:
    shares = [
        record["attn.select_tile_share"] for record in run.step_records
        if "attn.select_tile_share" in record
    ]
    return statistics.mean(shares) if shares else 1.0


def bound(run, kernel):
    sizes = run.config["sizes"]
    seq = run.seq_length()
    block = min(sizes["attention_block_size"], seq)
    cost = flops_keye.sel_kernel_cost(
        kernel, run.role.microbatch_rows_per_device(run.args),
        sizes["num_attention_heads"], sizes["num_key_value_heads"], seq,
        sizes["head_dim"], block, block, tile_share(run),
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
