"""Model FLOP/s utilisation of the accumulate program of the SwAV cell: the
analytic FLOPs of one device's micro-batch (``benchmark/flops_swav.py``: the
trunk's convolutions at every crop size, the projection MLP and the
prototypes a crop; recompute not counted) over the median device time of one
execution of the role's accumulate program (``step``), over the chip's bf16
peak (``benchmark/peaks.py``)."""
from benchmark import flops_swav, peaks
from benchmark.reducers import trace_program


def reduce(run, params):
    device_ms = trace_program.reduce(
        run, {"programs": ["accumulate"], "per": "execution"}
    )
    if not device_ms:
        return None
    per_sample = flops_swav.swav_train_flops_per_sample(run.config)
    rows = run.role.microbatch_rows_per_device(run.args)
    peak = peaks.chip_peaks(run.device_kind)["flops_per_s"]
    return 100.0 * per_sample * rows / (device_ms / 1e3) / peak
