"""Peak device memory on the fullest chip, in GB (run.py's accounting: the
allocator's own peak, or the live buffers at the end of the window plus the
accumulate program's scratch, whichever is larger)."""


def reduce(run, params):
    peak = run.memory.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
