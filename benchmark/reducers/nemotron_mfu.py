"""Model FLOP/s utilisation of the accumulate program of the Nemotron-H
cell: the analytic FLOPs of one device's micro-batch
(``benchmark/flops_nemotron.py``: the Mamba mixers' two projections and the
chunked scan's own products at the HELD heads and groups, attention at its
triangle at the held heads, the routers, the shared expert and the held
routed experts of TWO matrices at the expected share of slots, the untied
head over the slice; the element-wise prelude and recompute not counted)
over the median device time of one ``accumulate_step`` execution, over the
chip's bf16 peak (``benchmark/peaks.py``): the share of the whole step's
peak."""
from benchmark import flops_nemotron, peaks
from benchmark.reducers import trace_program


def reduce(run, params):
    device_ms = trace_program.reduce(
        run, {"programs": ["accumulate"], "per": "execution"}
    )
    if not device_ms:
        return None
    per_sample = flops_nemotron.nemotron_train_flops_per_sample(
        run.config["sizes"], run.seq_length()
    )
    rows = run.role.microbatch_rows_per_device(run.args)
    peak = peaks.chip_peaks(run.device_kind)["flops_per_s"]
    return 100.0 * per_sample * rows / (device_ms / 1e3) / peak
