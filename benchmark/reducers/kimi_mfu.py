"""Model FLOP/s utilisation of the accumulate program of the Kimi Linear
cell: the analytic FLOPs of one device's micro-batch
(``benchmark/flops_kimi.py``: the KDA mixers' projections and the chunked
rule's own products at the HELD heads, latent attention at its triangle, the
routed experts at the expected share of slots; the element-wise prelude and
recompute not counted) over the median device time of one
``accumulate_step`` execution, over the chip's bf16 peak
(``benchmark/peaks.py``): the share of the whole step's peak."""
from benchmark import flops_kimi, peaks
from benchmark.reducers import trace_program


def reduce(run, params):
    device_ms = trace_program.reduce(
        run, {"programs": ["accumulate"], "per": "execution"}
    )
    if not device_ms:
        return None
    per_sample = flops_kimi.kimi_train_flops_per_sample(
        run.config["sizes"], run.seq_length()
    )
    rows = run.role.microbatch_rows_per_device(run.args)
    peak = peaks.chip_peaks(run.device_kind)["flops_per_s"]
    return 100.0 * per_sample * rows / (device_ms / 1e3) / peak
