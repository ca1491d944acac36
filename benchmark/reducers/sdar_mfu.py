"""Model FLOP/s utilisation of the accumulate program of the block-diffusion
expert decoder's cell: the analytic FLOPs of one device's micro-batch
(``benchmark/flops_sdar.py``: projections, router and routed experts over
both streams' 2L positions, attention at its visible pairs, the head over the
noisy stream's L; recompute not counted) over the median device time of one
``accumulate_step`` execution, over the chip's bf16 peak
(``benchmark/peaks.py``). A row is L clean tokens: ``run.seq_length()``."""
from benchmark import flops_sdar, peaks
from benchmark.reducers import trace_program


def reduce(run, params):
    device_ms = trace_program.reduce(
        run, {"programs": ["accumulate"], "per": "execution"}
    )
    if not device_ms:
        return None
    per_sample = flops_sdar.sdar_train_flops_per_sample(
        run.config["sizes"], run.seq_length()
    )
    rows = run.role.microbatch_rows_per_device(run.args)
    peak = peaks.chip_peaks(run.device_kind)["flops_per_s"]
    return 100.0 * per_sample * rows / (device_ms / 1e3) / peak
