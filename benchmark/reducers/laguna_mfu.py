"""Model FLOP/s utilisation of the accumulate program of Laguna-XS.2's cell:
the analytic FLOPs of one device's micro-batch (``benchmark/
flops_laguna.py``: by kind of layer part, a full layer at its triangle and
48 heads, a sliding layer at its window and 64, the dense FFN, the routed
experts at the expected share of slots beside the shared expert, recompute
not counted) over the median device time of one ``accumulate_step``
execution, over the chip's bf16 peak (``benchmark/peaks.py``)."""
from benchmark import flops_laguna, peaks
from benchmark.reducers import trace_program


def reduce(run, params):
    device_ms = trace_program.reduce(
        run, {"programs": ["accumulate"], "per": "execution"}
    )
    if not device_ms:
        return None
    per_sample = flops_laguna.laguna_train_flops_per_sample(
        run.config["sizes"], run.seq_length()
    )
    rows = run.role.microbatch_rows_per_device(run.args)
    peak = peaks.chip_peaks(run.device_kind)["flops_per_s"]
    return 100.0 * per_sample * rows / (device_ms / 1e3) / peak
