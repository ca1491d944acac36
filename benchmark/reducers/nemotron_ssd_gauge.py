"""A state-space gauge of the program (``ssd.dt_mean.<layer>``,
``ssd.chunk_log_decay_min.<layer>``, ``ssd.state_abs_max.<layer>`` on the
window's step records, one entry a Mamba layer): ``params['gauge']`` folded
over the layers by ``params['over']`` (min / mean / max) and averaged over
the records — ``kimi_kda_gauge``'s reducer, which reads whatever gauge its
parameters name. A program without the gauge gives nothing."""
from benchmark.reducers.kimi_kda_gauge import FOLDS, reduce  # noqa: F401
