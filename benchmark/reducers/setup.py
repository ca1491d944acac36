"""Process start to the opening of the window: imports, DHT, init, compile
or cache load, the reference check, the warm-up global steps."""


def reduce(run, params):
    start, _end = run.window()
    return start - run.process_start
