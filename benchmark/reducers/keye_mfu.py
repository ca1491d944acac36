"""Model FLOP/s utilisation of the accumulate program of the selected-
attention expert decoder's cell: the analytic FLOPs of one device's
micro-batch (``benchmark/flops_keye.py``: attention at its SELECTED pairs,
the index scores over the triangle, routed experts at the expected share of
slots; the indexer's loss pass and recompute not counted) over the median
device time of one ``accumulate_step`` execution, over the chip's bf16 peak
(``benchmark/peaks.py``)."""
from benchmark import flops_keye, peaks
from benchmark.reducers import trace_program


def reduce(run, params):
    device_ms = trace_program.reduce(
        run, {"programs": ["accumulate"], "per": "execution"}
    )
    if not device_ms:
        return None
    per_sample = flops_keye.keye_train_flops_per_sample(
        run.config["sizes"], run.seq_length()
    )
    rows = run.role.microbatch_rows_per_device(run.args)
    peak = peaks.chip_peaks(run.device_kind)["flops_per_s"]
    return 100.0 * per_sample * rows / (device_ms / 1e3) / peak
