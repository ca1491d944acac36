"""Model FLOP/s utilisation of the accumulate program alone: the analytic
ALBERT FLOPs of one device's micro-batch (``benchmark/flops.py``, recompute
not counted) over the median device time of one ``accumulate_step``
execution, over the chip's bf16 peak (``benchmark/peaks.py``)."""
from benchmark import flops, peaks
from benchmark.reducers import trace_program


def reduce(run, params):
    device_ms = trace_program.reduce(
        run, {"programs": ["accumulate"], "per": "execution"}
    )
    if not device_ms:
        return None
    sizes = run.config["sizes"]
    seq = run.seq_length()
    per_sample = flops.albert_train_flops_per_sample(
        sizes["hidden_size"], sizes["intermediate_size"],
        sizes["embedding_size"], sizes["vocab_size"],
        sizes["num_hidden_layers"], seq, flops.max_predictions_for(seq),
    )
    rows = run.role.microbatch_rows_per_device(run.args)
    peak = peaks.chip_peaks(run.device_kind)["flops_per_s"]
    return 100.0 * per_sample * rows / (device_ms / 1e3) / peak
