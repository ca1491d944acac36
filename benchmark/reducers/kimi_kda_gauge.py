"""A KDA gauge of the program (``kda.chunk_log_decay_min.<layer>``,
``kda.beta_mean.<layer>``, ``kda.state_abs_max.<layer>`` on the window's
step records, one entry a KDA layer): ``params['gauge']`` folded over the
layers by ``params['over']`` (min / mean / max) and averaged over the
records. A program without the gauge gives nothing."""
import statistics

FOLDS = {"min": min, "max": max, "mean": statistics.mean}


def reduce(run, params):
    prefix = params["gauge"] + "."
    per_record = [
        FOLDS[params["over"]](
            [value for key, value in record.items() if key.startswith(prefix)]
        )
        for record in run.step_records
        if any(key.startswith(prefix) for key in record)
    ]
    return statistics.mean(per_record) if per_record else None
