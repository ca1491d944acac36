"""Model FLOP/s utilisation of the accumulate program of a causal-LM cell:
the analytic FLOPs of one device's micro-batch (``benchmark/flops_lm.py``:
causal attention at its triangle, recompute not counted) over the median
device time of one ``accumulate_step`` execution, over the chip's bf16 peak
(``benchmark/peaks.py``)."""
from benchmark import flops_lm, peaks
from benchmark.reducers import trace_program


def reduce(run, params):
    device_ms = trace_program.reduce(
        run, {"programs": ["accumulate"], "per": "execution"}
    )
    if not device_ms:
        return None
    sizes = run.config["sizes"]
    per_sample = flops_lm.ouro_train_flops_per_sample(
        sizes["hidden_size"], sizes["intermediate_size"],
        sizes["num_attention_heads"], sizes["head_dim"], sizes["vocab_size"],
        sizes["num_hidden_layers"], sizes["total_ut_steps"], run.seq_length(),
    )
    rows = run.role.microbatch_rows_per_device(run.args)
    peak = peaks.chip_peaks(run.device_kind)["flops_per_s"]
    return 100.0 * per_sample * rows / (device_ms / 1e3) / peak
