"""One part of ``setup_s`` off the program's own ``set-up:`` line (the
set-up record of ``telemetry/steps.py``: role entry to the end of the first
global step, logged once a peer when it closes; the harness's log listener
keeps it, stamped on the harness's clock at the record's close).

``part``: ``role`` the record's ``total``; ``harness`` process start to the
record's opening (the stamp minus ``total``, minus the process start);
``warmup`` the record's close to the window's opening (the metric lists the
cells that warm up for MORE than one global step: where the first global
step is the warm-up, the window opens at that ``opt.step``'s return, a
``post_step`` BEFORE the record closes, and role + harness is ``setup_s``
plus those milliseconds);
``first_calls`` the line's ``first_calls``; ``sum`` the line's values under
``keys`` (a key the line lacks counts 0). In a cell of several peers every part is read off ONE
line, the slowest peer's (the last to close: the window waits for it), so
harness + role + warmup is ``setup_s`` exactly. A program that logs no such
line gives nothing."""


def parse_line(message):
    """``set-up: key=value key=value | key=value ...`` -> {key: float}, or
    None for any other message."""
    head, colon, rest = message.partition(":")
    if head != "set-up" or not colon:
        return None
    values = {}
    for token in rest.split():
        key, equals, value = token.rpartition("=")
        if not equals:
            continue  # the bar between the laps and the compile sums
        try:
            values[key] = float(value)
        except ValueError:
            continue
    return values


def slowest_line(run):
    """(t_close, values) of the complete set-up line that closed last (a
    peer logs one; where the cell warms up for ONE global step it closes a
    ``post_step`` after the window has opened), or None."""
    best = None
    for t, _level, _peer, _name, message in run.recorder.log:
        values = parse_line(message)
        if (
            values and values.get("complete") and "total" in values
            and (best is None or t > best[0])
        ):
            best = (t, values)
    return best


def reduce(run, params):
    found = slowest_line(run)
    if found is None:
        return None
    t_close, values = found
    part = params["part"]
    if part == "role":
        return values["total"]
    if part == "harness":
        return t_close - values["total"] - run.process_start
    if part == "warmup":
        return run.window()[0] - t_close
    if part == "first_calls":
        return values.get("first_calls")
    return sum(values.get(key, 0.0) for key in params["keys"])
