"""Samples per second per chip through the whole collaborative step.

The metric is the WHOLE window's rate: the rows drawn by all peers between
the window's opening and closing global step (each peer's rows between ITS
completions of the two — samples are counted where they are drawn), over
that wall, over the cell's chips. A stall in one global step is in it, as a
volunteer feels it. ``step_rates`` gives the same rate per global step; the
run prints its least, median and greatest beside the metric, so a stall can
be told from a uniformly slower step."""


def step_rates(run):
    recorder = run.recorder
    rates = []
    steps = range(recorder.start_step + 1, recorder.final_step + 1)
    for step in steps:
        rows, starts, ends = 0, [], []
        for peer in recorder.peers:
            t_prev, t_done = peer.step_time(step - 1), peer.step_time(step)
            if t_prev is None or t_done is None:
                return []
            rows += sum(n for _t0, t1, n in peer.draws if t_prev < t1 <= t_done)
            starts.append(t_prev)
            ends.append(t_done)
        rates.append(rows / (max(ends) - max(starts)) / run.chips)
    return rates


def whole_window_rate(run):
    start, end = run.window()
    rows = sum(n for _seconds, n in run.draws_in_window())
    return rows / (end - start) / run.chips


def reduce(run, params):
    return whole_window_rate(run)
