"""1 - busy / window on the device that idled most, in percent."""
from benchmark import trace as T


def reduce(run, params):
    busy = T.device_busy(run.trace or {})
    if not busy:
        return None
    return max(100.0 * (1.0 - b / w) for b, w in busy.values() if w > 0)
