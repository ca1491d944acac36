"""Collective ops' share of the device's busy time, worst device."""
from benchmark import trace as T


def reduce(run, params):
    busy = T.device_busy(run.trace or {})
    collective = T.collective_seconds(run.trace or {})
    shares = [
        100.0 * collective.get(device, 0.0) / b
        for device, (b, _w) in busy.items() if b > 0
    ]
    return max(shares) if shares else None
